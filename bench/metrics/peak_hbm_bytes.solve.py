"""``peak_hbm_bytes.solve``: the chip's ``peak_bytes_in_use`` after the
window, as ``memory_stats()`` reports it (the fullest chip)."""


def read(run):
    return run.peak_bytes
