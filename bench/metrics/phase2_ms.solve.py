"""``phase2_ms.solve``: device milliseconds per traced solve in phase 2,
the device part of ``certificate_s`` (scope ``wbpr.phase2``, program
``jit_phase2_impl``), from the profiler trace's ops (``op_scopes``)."""
import op_scopes


def read(run):
    return op_scopes.phase_ms(run, "phase2")
