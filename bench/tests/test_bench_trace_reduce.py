"""The trace reduction, on a hand-made trace whose numbers can be worked
out by hand and on a small trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

import trace_reduce as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def hand_trace():
    """Window 0..100 ns.  Program A runs 10..40 with ops 10..20 and
    15..30 (overlapping); program B runs 60..80 with op 60..80.  The host
    is in span ``bench.solve`` over 0..50 and ``bench.certificate`` over
    50..100."""
    return [
        (HOST, "python", "bench.traced", 0.0, 100.0),
        (HOST, "python", "bench.solve", 0.0, 50.0),
        (HOST, "python", "bench.certificate", 50.0, 50.0),
        (HOST, "python", "not.ours", 0.0, 100.0),
        (DEV, "XLA Modules", "jit_a(123)", 10.0, 30.0),
        (DEV, "XLA Modules", "jit_b(456)", 60.0, 20.0),
        (DEV, "XLA Ops", "%fusion.1 = s32[4] fusion(x)", 10.0, 10.0),
        (DEV, "XLA Ops", "%while.2 = (s32[]) while(y)", 15.0, 15.0),
        (DEV, "XLA Ops", "%copy.3 = s32[4] copy(z)", 60.0, 20.0),
        (DEV, "Async XLA Ops", "%copy-start = s32[4] copy-start(z)",
         35.0, 50.0),
    ]


def test_hand_trace():
    r = tr.reduce_events(hand_trace())
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(40e-9)  # 10..30 and 60..80
    assert r.programs == {"jit_a": [pytest.approx(30e-9), 1],
                          "jit_b": [pytest.approx(20e-9), 1]}
    assert r.ops == {"jit_a:%fusion.1": pytest.approx(10e-9),
                     "jit_a:%while.2": pytest.approx(15e-9),
                     "jit_b:%copy.3": pytest.approx(20e-9)}
    # idle gaps 0..10 and 30..60 (midpoint 45, inside solve) and 80..100
    # (certificate): a whole gap goes to the span that holds its midpoint
    assert r.gaps == {"bench.solve": pytest.approx(40e-9),
                      "bench.certificate": pytest.approx(20e-9)}
    assert r.program_s(["jit_a", "jit_c"]) == pytest.approx(30e-9)
    assert r.program_s(["jit_c"]) is None
    b = r.breakdown(top=2)
    assert [k for k, _ in b["device_ops"]] == ["jit_b:%copy.3",
                                              "jit_a:%while.2"]
    assert len(b["idle_gaps"]) == 2


def test_no_device_events():
    with pytest.raises(ValueError):
        tr.reduce_events([(HOST, "python", "bench.traced", 0.0, 1.0)])


RECORDED = Path(__file__).parent / "data" / "trace_small.json"


def test_recorded_trace():
    """A trace of one cold solve of washington_rlg(16, 3) on a TPU v5e,
    recorded through the benchmark's spans: its numbers, recomputed here
    the slow way."""
    rec = json.loads(RECORDED.read_text())
    ev = [tuple(e) for e in rec["events"]]
    r = tr.reduce_events(ev)
    (w0, w1), = [(e[3], e[3] + e[4]) for e in ev if e[2] == "bench.traced"]
    ops = [(e[3], e[3] + e[4]) for e in ev if e[1] == "XLA Ops"]
    # busy by brute force over the integer nanoseconds of the window
    busy = set()
    for a, b in ops:
        busy.update(range(int(max(a, w0)), int(min(b, w1))))
    assert r.busy_s == pytest.approx(len(busy) * 1e-9, rel=1e-3)
    assert r.window_s == pytest.approx((w1 - w0) * 1e-9)
    mods = [e for e in ev if e[1] == "XLA Modules"]
    assert sum(p[1] for p in r.programs.values()) == len(mods)
    assert r.program_s(["jit_run_cycles"]) > 0
    assert sum(r.ops.values()) == pytest.approx(
        sum(e[4] for e in ev if e[1] == "XLA Ops") * 1e-9)
    assert sum(r.gaps.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    assert rec["expected"]["busy_s"] == pytest.approx(r.busy_s)
