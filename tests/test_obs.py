"""Unified telemetry subsystem: metrics registry, span tracer,
device-side solver counters, serving snapshot.

The load-bearing contracts:

* counter parity — telemetry solves report bit-identical
  push/relabel/active/frontier counts across every step mode on the same
  instance (the state sequences are identical, so the counters must be);
* the counting identity — every valid active vertex does exactly one
  push or one relabel per bulk-synchronous cycle, so
  ``pushes + relabels == sum(active_history)`` always;
* disabled purity — ``telemetry=False`` traces contain strictly fewer
  equations (nothing telemetry-shaped left behind) and the same number
  of ``pallas_call``s, and retrace deterministically;
* every ``stats()`` / ``telemetry_snapshot()`` tree JSON round-trips.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.analysis import ir
from repro.core import batched
from repro.core import pushrelabel as pr
from repro.core.csr import build_residual
from repro.graphs import generators as G
from repro.obs import REGISTRY, TRACER, scopes, span, to_jsonable
from repro.obs.metrics import MetricsRegistry
from tests.conftest import random_graph

MODES = ("vc", "tc", "vc_kernel", "vc_kernel_bsearch")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Process-global registry/tracer: leave no state behind."""
    REGISTRY.reset()
    TRACER.disable()
    TRACER.clear()
    yield
    REGISTRY.reset()
    TRACER.disable()
    TRACER.clear()


# -- metrics registry ---------------------------------------------------------


def test_metrics_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("req", route="a")
    c.inc()
    c.inc(2)
    assert c.value == 3
    assert reg.counter("req", route="a") is c  # same labels -> same metric
    assert reg.counter("req", route="b") is not c
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value == 4
    h = reg.histogram("lat_s", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 3
    assert h.mean == pytest.approx(5.55 / 3)
    snap = reg.snapshot()
    json.dumps(snap)  # must be JSON-clean
    assert snap["counters"]["req{route=a}"] == 3
    assert snap["gauges"]["depth"] == 4
    hs = snap["histograms"]["lat_s"]
    assert hs["counts"] == [1, 1, 1]  # <=0.1, <=1.0, +inf
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_metrics_label_keys_sorted_and_stable():
    reg = MetricsRegistry()
    reg.counter("x", b="2", a="1").inc()
    assert list(reg.snapshot()["counters"]) == ["x{a=1,b=2}"]


# -- span tracer --------------------------------------------------------------


def test_trace_disabled_is_inert():
    with span("never", a=1):
        pass
    TRACER.complete("no", 0.0, 1.0)
    assert len(TRACER) == 0


def test_trace_nested_spans_export(tmp_path):
    TRACER.enable()
    with span("outer", k="v"):
        with span("inner"):
            pass
    TRACER.complete("life", 0.001, 0.003, id="r1")
    path = TRACER.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        data = json.load(f)
    evs = data["traceEvents"]
    assert [e["ph"] for e in evs] == ["B", "B", "E", "E", "X"]
    assert [e["name"] for e in evs[:4]] == ["outer", "inner", "inner",
                                            "outer"]  # properly nested
    assert evs[0]["args"] == {"k": "v"}
    x = evs[4]
    assert x["dur"] == pytest.approx(2000.0)  # us
    # timestamps monotonic within the span tree
    assert evs[0]["ts"] <= evs[1]["ts"] <= evs[2]["ts"] <= evs[3]["ts"]


def test_trace_spans_on_the_profilers_clock(tmp_path):
    """A span entered under ``jax.profiler.trace`` is an event on the
    ``/host:CPU`` plane, within 1 ms of the enabled tracer's Chrome
    ``ts`` for it; a count set inside the span reaches both."""
    import glob

    import jax
    from jax.profiler import ProfileData

    TRACER.enable()
    with jax.profiler.trace(str(tmp_path)):
        with span("obs.clock", k="v") as sp:
            sp.set_metadata(cycles=7)
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(path)
    start = dict(pd.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    host = [e for line in pd.find_plane_with_name("/host:CPU").lines
            for e in line.events if e.name == "obs.clock"]
    assert len(host) == 1
    begin, end = [e for e in TRACER.to_dict()["traceEvents"]
                  if e["name"] == "obs.clock"]
    assert abs((start + host[0].start_ns) * 1e-3 - begin["ts"]) < 1000.0
    assert end["args"] == {"cycles": 7}
    assert dict(host[0].stats).get("cycles") in (7, "7")


def test_trace_complete_on_the_profilers_clock():
    """``complete`` takes ``perf_counter`` endpoints and records them on
    the profiler's clock (``time.time_ns``)."""
    import time

    TRACER.enable()
    now_us = time.time_ns() * 1e-3
    t = time.perf_counter()
    TRACER.complete("life", t - 0.5, t)
    (ev,) = TRACER.to_dict()["traceEvents"]
    assert abs(ev["ts"] - (now_us - 5e5)) < 1000.0
    assert ev["dur"] == pytest.approx(5e5)


# -- device scopes ------------------------------------------------------------


@pytest.mark.parametrize("op_name,phase", [
    ("jit(run_cycles)/wbpr.cycle/loop/while/body/wbpr.cycle/minh/scatter",
     "minh"),
    ("jit(run_cycles)/wbpr.cycle/loop/while/body/select_n", "loop"),
    ("jit(phase2_impl)/wbpr.phase2/while/body/wbpr.cycle/frontier/gather",
     "phase2"),
    ("jit(f)/wbpr.global_relabel/while/wbpr.cycle/apply/add",
     "global_relabel"),
    ("jit(f)/wbpr.cycle/minhx/add", None),
    ("jit(run_cycles)/while/body/add", None),
])
def test_phase_of(op_name, phase):
    """A program scope claims all inside it; else the innermost cycle
    scope wins."""
    assert scopes.phase_of(op_name) == phase


def _run_computations(hlo_text):
    """Instruction -> opcode over the computations a program runs as
    such: its entry and what its control flow calls (the test's own
    reading of the HLO text)."""
    import re

    comps, calls, entry, cur = {}, {}, None, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            cur = head.group(2)
            comps[cur], calls[cur] = {}, []
            entry = cur if head.group(1) else entry
            continue
        inst = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(",
                        line)
        if cur and inst:
            comps[cur][inst.group(1)] = inst.group(2)
            calls[cur] += re.findall(
                r"(?:body|condition|true_computation|false_computation)"
                r"=%([\w.\-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                    line):
                calls[cur] += re.findall(r"%([\w.\-]+)", group)
    out, todo = {}, [entry]
    while todo:
        c = todo.pop()
        if c in comps and not set(comps[c]) <= set(out):
            out.update(comps[c])
            todo += calls[c]
    return out


def test_op_scopes_cover_the_solve_programs():
    """Every instruction the cycle loop, the global relabel and phase 2
    run gets a phase (containers none); the cycle program holds all five
    step phases, the others their own."""
    from repro.api import MaxflowProblem, SolverOptions

    g, s, t = G.washington_rlg(16, 3)
    texts = scopes.solve_hlo(MaxflowProblem(g, s, t), SolverOptions())
    assert set(texts) == {"jit_run_cycles", "jit_global_relabel_impl",
                          "jit_phase2_impl"}
    want = {"jit_run_cycles": {"compact", "frontier", "minh", "apply",
                               "loop"},
            "jit_global_relabel_impl": {"global_relabel"},
            "jit_phase2_impl": {"phase2"}}
    for prog, text in texts.items():
        got = scopes.op_scopes(text)
        insts = _run_computations(text)
        assert set(got) == set(insts), prog
        for name, opcode in insts.items():
            if opcode in scopes.CONTAINERS:
                assert got[name] is None, (prog, name)
            else:
                assert got[name] in scopes.PHASES, (prog, name, opcode)
        assert set(got.values()) - {None} == want[prog], prog


def test_op_scopes_cover_the_bucketed_cycle_program():
    """The ``vc`` cycle loop of a graph with several frontier rungs runs
    its step in the branches of a ``conditional``: every instruction of
    it still gets a phase, and all five step phases are there."""
    from repro.api import MaxflowProblem, SolverOptions

    g, s, t = G.washington_rlg(128, 8)
    r = MaxflowProblem(g, s, t).residual("bcsr")
    assert len(pr.frontier_ladder(r.n, r.num_arcs)) > 1
    text = scopes.solve_hlo(MaxflowProblem(g, s, t),
                            SolverOptions())["jit_run_cycles"]
    assert " conditional(" in text
    got = scopes.op_scopes(text)
    insts = _run_computations(text)
    assert set(got) == set(insts)
    for name, opcode in insts.items():
        if opcode in scopes.CONTAINERS:
            assert got[name] is None, name
        else:
            assert got[name] in scopes.PHASES, (name, opcode)
    assert set(got.values()) - {None} == {"compact", "frontier", "minh",
                                          "apply", "loop"}


def test_op_scopes_without_scopes_place_nothing():
    """A program compiled without the scopes maps its containers alone,
    so a reader finds none of its ops."""
    import jax
    import jax.numpy as jnp

    def f(x):
        return jax.lax.while_loop(lambda c: c[0] < 3,
                                  lambda c: (c[0] + 1, jnp.cumsum(c[1])),
                                  (0, x))[1]

    text = jax.jit(f).lower(jnp.arange(8)).compile().as_text()
    got = scopes.op_scopes(text)
    assert got and set(got.values()) == {None}


# -- to_jsonable --------------------------------------------------------------


def test_to_jsonable_round_trip():
    from repro.serving.queueing import BucketKey

    @dataclasses.dataclass
    class Thing:
        a: int
        b: tuple

    tree = {
        BucketKey(64, 256, 8): {"arr": np.arange(3, dtype=np.int32),
                                "scalar": np.int64(7),
                                "f": np.float32(0.5)},
        "t": Thing(1, (2, 3)),
        "set": {1},
        ("tuple", "key"): None,
    }
    out = to_jsonable(tree)
    json.dumps(out)  # the contract
    assert out["n64a256d8"] == {"arr": [0, 1, 2], "scalar": 7, "f": 0.5}
    assert out["t"] == {"a": 1, "b": [2, 3]}
    assert out["set"] == [1]


# -- device-side solver counters ---------------------------------------------


def test_counter_parity_across_modes(rng):
    """Same instance, every step mode: identical per-cycle telemetry —
    and the one-push-or-one-relabel-per-active-vertex identity."""
    g = random_graph(rng, n_lo=14, n_hi=22)
    r = build_residual(g, "bcsr")
    s, t = 0, g.n - 1
    base = None
    for mode in MODES:
        st = pr.solve_impl(r, s, t, mode=mode, instrument=True)
        assert st.pushes + st.relabels == int(st.active_history.sum())
        assert len(st.active_history) == st.cycles
        assert len(st.frontier_history) == st.cycles
        cur = (st.maxflow, st.pushes, st.relabels, st.gr_sweeps,
               st.active_history.tolist(), st.frontier_history.tolist(),
               st.maxdeg_history.tolist())
        if base is None:
            base = cur
        else:
            assert cur == base, f"mode {mode} diverged from {MODES[0]}"
    assert base[1] > 0  # pushes: a live solve counted real work
    # telemetry off: same flow, empty histories
    off = pr.solve_impl(r, s, t, mode="vc")
    assert off.maxflow == base[0]
    assert off.pushes == 0 and len(off.active_history) == 0


def test_batched_counter_parity(rng):
    insts = []
    for _ in range(3):
        g = random_graph(rng, n_lo=10, n_hi=18)
        insts.append((build_residual(g, "bcsr"), 0, g.n - 1))
    base = None
    for mode in ("vc", "vc_kernel", "vc_kernel_bsearch"):
        out = batched.batched_solve_impl(insts, mode=mode, telemetry=True)
        assert (out.pushes + out.relabels == out.active_sum).all()
        cur = (out.maxflows.tolist(), out.pushes.tolist(),
               out.relabels.tolist(), out.frontier_sum.tolist(),
               out.gr_sweeps)
        if base is None:
            base = cur
        else:
            assert cur == base, f"mode {mode} diverged"
    off = batched.batched_solve_impl(insts, mode="vc")
    assert off.pushes is None and off.relabels is None
    assert off.maxflows.tolist() == base[0]


def test_disabled_telemetry_trace_is_lean(rng):
    """telemetry=False must not leave counter plumbing in the trace:
    strictly fewer equations than telemetry=True, identical pallas_call
    count, and a deterministic retrace."""
    g = random_graph(rng, n_lo=10, n_hi=14)
    r = build_residual(g, "bcsr")
    dg, meta, res0 = pr.to_device(r)
    state = pr.preflow(dg, meta, res0, 0)
    t = g.n - 1

    def eqns(mode, telemetry):
        jx = ir.trace(
            lambda st: pr.run_cycles(dg, meta, st, 0, t, mode=mode,
                                     max_cycles=8, telemetry=telemetry),
            state)
        census = ir.census_of(jx)
        return census.eqn_count, census.pallas_call_count, str(jx)

    for mode in ("vc", "vc_kernel"):
        off_n, off_p, off_s = eqns(mode, False)
        on_n, on_p, _ = eqns(mode, True)
        assert off_n < on_n, (mode, off_n, on_n)
        assert off_p == on_p, (mode, off_p, on_p)
        # retrace determinism: the disabled path is stable
        assert eqns(mode, False)[2] == off_s


def test_frontier_lanes_counter():
    """The lanes the executed cycles ran bound the frontier they scanned
    and are bounded by the padded A per cycle; the bucketed ``vc`` loop
    runs fewer, the batched driver exactly A_pad per live cycle."""
    from repro.api import MaxflowProblem, Solver, SolverOptions

    g, s, t = G.washington_rlg(128, 8)
    problem = MaxflowProblem(g, s, t)
    A = problem.residual("bcsr").num_arcs
    st = Solver(SolverOptions(telemetry=True)).solve(problem).stats
    frontier = int(st.frontier_history.sum())
    assert 0 < frontier <= st.frontier_lanes < st.cycles * A
    for mode in ("tc", "vc_kernel"):
        pad = Solver(SolverOptions(mode=mode, telemetry=True)).solve(
            problem).stats
        assert pad.frontier_lanes == pad.cycles * A, mode
    off = Solver().solve(problem).stats
    assert off.frontier_lanes == 0
    insts = []
    for seed in range(3):
        g, s, t = G.washington_rlg(32, 4, seed=seed)
        insts.append((build_residual(g, "bcsr"), s, t))
    out = batched.batched_solve_impl(insts, mode="vc", telemetry=True)
    a_pad = out.state.res.shape[1]
    assert (out.frontier_lanes == a_pad * out.cycles).all()
    assert (out.frontier_sum <= out.frontier_lanes).all()


def test_api_telemetry_stats():
    from repro.api import MaxflowProblem, Solver, SolverOptions

    g, s, t = G.powerlaw(80, 2, seed=3)
    sol = Solver(SolverOptions(telemetry=True)).solve(
        MaxflowProblem(g, s, t))
    st = sol.stats
    assert st.pushes > 0
    assert st.pushes + st.relabels == int(st.active_history.sum())
    assert len(st.active_history) == st.cycles
    off = Solver().solve(MaxflowProblem(g, s, t))
    assert off.value == sol.value
    assert off.stats.active_history is None
    # batched backend: per-instance totals, no histories
    many = Solver(SolverOptions(backend="batched", telemetry=True)).solve(
        MaxflowProblem(g, s, t))
    assert many.value == sol.value
    assert many.stats.pushes > 0 and many.stats.active_history is None


# -- serving snapshot ---------------------------------------------------------


def _small_service_graphs():
    return [G.powerlaw(60, 2, seed=seed) for seed in range(5)]


def test_service_telemetry_snapshot():
    from repro.serving import MaxflowService, ServiceConfig

    TRACER.enable()
    svc = MaxflowService(ServiceConfig(mode="vc", max_batch=4))
    futs = [svc.submit(g, s, t) for g, s, t in _small_service_graphs()]
    svc.flush()
    flows = [f.result().maxflow for f in futs]
    snap = svc.telemetry_snapshot()
    json.dumps(snap)  # the round-trip contract
    bcs = snap["stats"]["bucket_counters"]
    assert bcs
    for lbl, bc in bcs.items():
        assert bc["pushes"] + bc["relabels"] == bc["active_sum"], (lbl, bc)
    assert sum(bc["pushes"] for bc in bcs.values()) > 0
    counters = snap["metrics"]["counters"]
    assert any(k.startswith("serve.pushes{bucket=") for k in counters)
    assert counters["serve.result_cache.misses"] == len(futs)
    # span tree: balanced B/E, one request lifecycle per served request
    evs = TRACER.to_dict()["traceEvents"]
    phs = [e["ph"] for e in evs]
    assert phs.count("B") == phs.count("E") > 0
    reqs = [e for e in evs if e["ph"] == "X" and e["name"] == "serve.request"]
    assert len(reqs) == len(futs)
    # telemetry off: same flows, no device counters in the bucket table
    svc2 = MaxflowService(ServiceConfig(mode="vc", max_batch=4,
                                        telemetry=False))
    futs2 = [svc2.submit(g, s, t) for g, s, t in _small_service_graphs()]
    svc2.flush()
    assert [f.result().maxflow for f in futs2] == flows
    for bc in svc2.stats()["bucket_counters"].values():
        assert "pushes" not in bc
    json.dumps(svc2.telemetry_snapshot())
