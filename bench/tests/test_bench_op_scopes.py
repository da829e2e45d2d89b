"""The phase readers (``step_*``, ``global_relabel_ms``, ``phase2_ms``) on
a hand-built trace reduction and hand-written HLO text, whose numbers
can be worked out by hand."""
import pytest

import op_scopes
import run
import trace_reduce as tr

CYCLES = """HloModule jit_run_cycles, is_scheduled=true

%fused_minh (p.1: s32[8]) -> s32[8] {
  %p.1 = s32[8]{0} parameter(0)
  ROOT %neg.1 = s32[8]{0} negate(%p.1), metadata={op_name="jit(run_cycles)/wbpr.cycle/loop/while/body/wbpr.cycle/minh/neg"}
}

%body (c.1: (s32[8], s32[])) -> (s32[8], s32[]) {
  %c.1 = (s32[8]{0}, s32[]) parameter(0)
  %x.1 = s32[8]{0} get-tuple-element(%c.1), index=0
  %i.1 = s32[] get-tuple-element(%c.1), index=1
  %nonzero.1 = s32[8]{0} sort(%x.1), dimensions={0}, metadata={op_name="jit(run_cycles)/wbpr.cycle/loop/while/body/wbpr.cycle/compact/sort"}
  %copy.1 = s32[8]{0} copy(%nonzero.1)
  %gather.1 = s32[8]{0} add(%copy.1, %x.1), metadata={op_name="jit(run_cycles)/wbpr.cycle/loop/while/body/wbpr.cycle/frontier/add"}
  %fusion.1 = s32[8]{0} fusion(%gather.1), kind=kLoop, calls=%fused_minh
  %scatter.1 = s32[8]{0} multiply(%fusion.1, %x.1), metadata={op_name="jit(run_cycles)/wbpr.cycle/loop/while/body/wbpr.cycle/apply/mul"}
  %one.1 = s32[] constant(1)
  %add.1 = s32[] add(%i.1, %one.1), metadata={op_name="jit(run_cycles)/wbpr.cycle/loop/while/body/add"}
  ROOT %tuple.1 = (s32[8]{0}, s32[]) tuple(%scatter.1, %add.1)
}

%cond (c.2: (s32[8], s32[])) -> pred[] {
  %c.2 = (s32[8]{0}, s32[]) parameter(0)
  %i.2 = s32[] get-tuple-element(%c.2), index=1
  %ten.2 = s32[] constant(10)
  ROOT %lt.2 = pred[] compare(%i.2, %ten.2), direction=LT, metadata={op_name="jit(run_cycles)/wbpr.cycle/loop/while/lt"}
}

ENTRY %main (x.3: s32[8]) -> s32[8] {
  %x.3 = s32[8]{0} parameter(0)
  %zero.3 = s32[] constant(0)
  %tuple.3 = (s32[8]{0}, s32[]) tuple(%x.3, %zero.3)
  %while.3 = (s32[8]{0}, s32[]) while(%tuple.3), condition=%cond, body=%body, metadata={op_name="jit(run_cycles)/wbpr.cycle/loop/while"}
  ROOT %out.3 = s32[8]{0} get-tuple-element(%while.3), index=0
}
"""

RELABEL = """HloModule jit_global_relabel_impl, is_scheduled=true

ENTRY %main (x.4: s32[8]) -> s32[8] {
  %x.4 = s32[8]{0} parameter(0)
  ROOT %min.4 = s32[8]{0} negate(%x.4), metadata={op_name="jit(global_relabel_impl)/wbpr.global_relabel/neg"}
}
"""

PHASE2 = """HloModule jit_phase2_impl, is_scheduled=true

ENTRY %main (x.5: s32[8]) -> s32[8] {
  %x.5 = s32[8]{0} parameter(0)
  ROOT %neg.5 = s32[8]{0} negate(%x.5), metadata={op_name="jit(phase2_impl)/wbpr.phase2/wbpr.cycle/minh/neg"}
}
"""

TEXTS = {"jit_run_cycles": CYCLES, "jit_global_relabel_impl": RELABEL,
         "jit_phase2_impl": PHASE2}

#: device seconds per op over two traced solves
OPS = {
    "jit_run_cycles:%while.3": 1.0,  # a container: no phase
    "jit_run_cycles:%nonzero.1": 0.010,
    "jit_run_cycles:%copy.1": 0.002,  # no metadata: its user's (frontier)
    "jit_run_cycles:%gather.1": 0.020,
    "jit_run_cycles:%fusion.1": 0.040,  # the fused root's (minh)
    "jit_run_cycles:%scatter.1": 0.080,
    "jit_run_cycles:%add.1": 0.004,
    "jit_run_cycles:%lt.2": 0.002,
    "jit_global_relabel_impl:%min.4": 0.006,
    "jit_phase2_impl:%neg.5": 0.100,  # phase 2 claims its cycle helpers
    "jit_scatter-add:%scatter.9": 5.0,  # not a solve program
}

#: metric -> device ms per solve
WANT = {"step_compact_ms.solve": 5.0, "step_frontier_ms.solve": 11.0,
        "step_minh_ms.solve": 20.0, "step_apply_ms.solve": 40.0,
        "step_loop_ms.solve": 3.0, "global_relabel_ms.solve": 3.0,
        "phase2_ms.solve": 50.0}


class FakeRun:
    """What the readers use of ``run.Run``: a trace of two solves."""

    def __init__(self, ops):
        self.trace = tr.Reduction(window_s=2.0, busy_s=1.5, programs={},
                                  ops=ops, gaps={})
        self.spans = {"solve": [1.0, 1.0]}
        self.driver_state = {}


def reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py",
                           f"bench_metric_{name}")


@pytest.fixture
def hand_hlo(monkeypatch):
    monkeypatch.setattr(op_scopes, "program_hlo", lambda r: dict(TEXTS))


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(hand_hlo, name):
    assert reader(name).read(FakeRun(dict(OPS))) == pytest.approx(
        WANT[name])


def test_unknown_op_gives_none(hand_hlo):
    """An op of a mapped program that the map does not hold leaves every
    reader without a number."""
    r = FakeRun({**OPS, "jit_run_cycles:%fusion.77": 0.5})
    for name in WANT:
        assert reader(name).read(r) is None, name


def test_program_without_scopes_gives_none(monkeypatch):
    """No ``repro.obs.scopes`` in the program (or no HLO): None."""
    monkeypatch.setattr(op_scopes, "program_hlo", lambda r: None)
    for name in WANT:
        assert reader(name).read(FakeRun(dict(OPS))) is None, name


def test_phase_seconds_needs_a_mapped_program():
    assert op_scopes.phase_seconds({"jit_other:%x": 1.0},
                                   {"jit_run_cycles": {}}, "minh") is None
