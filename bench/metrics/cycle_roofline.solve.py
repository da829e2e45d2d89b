"""``cycle_roofline.solve``: the cycle loop's share of its roofline, in %.

The least bytes the traced solve's cycles have to move
(``cycle_bytes.least_bytes`` over the per-cycle active-vertex and
frontier-arc counts of a telemetry solve of the same instance in set-up)
over the chip's HBM bandwidth (``peaks.json``), divided by the device
time of the cycle-loop program per solve.  Bandwidth bounds it: a cycle
does no arithmetic worth counting."""
import importlib.util
from pathlib import Path

import cycle_bytes


def read(run):
    here = Path(__file__).parent
    spec = importlib.util.spec_from_file_location(
        "bench_metric_cycle_loop_ms_solve", here / "cycle_loop_ms.solve.py")
    loop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loop)
    ms = loop.read(run)
    st = run.driver_state
    if ms is None or st.get("active") is None:
        return None
    least = cycle_bytes.least_bytes(st["active"], st["frontier"])
    bw = cycle_bytes.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (least / bw) / (ms * 1e-3)
