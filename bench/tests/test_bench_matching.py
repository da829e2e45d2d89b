"""The matching cell on the CPU: the copied generator gives the program's
graphs, a small run of ``bip.cold`` is correct with nothing compiled in
its window, the control and planted faults make it not correct, and the
comparison's extra graph keeps the timed graph's shapes while neither
trivial cut of it is minimum."""
import dataclasses
import importlib.util

import numpy as np
import pytest
from repro.api import Solver
from repro.core.csr import build_residual
from repro.graphs import generators as G

import bipartite
import facade
import faults
import faults_matching
import reference
import reference_matching
import run

CELL = "bip.cold"
#: the cell cut for the CPU: the configuration's shape, 1/64 of its users
SMALL = {"n_left": 184, "n_right": 59, "n_edges": 573}


def _driver():
    path = run.BENCH / "drivers" / "matching_cold_solves.py"
    spec = importlib.util.spec_from_file_location("bench_matching_driver",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("size,seed", [((30, 20, 80, 0.5, 0.8), 0),
                                       ((60, 10, 100, 0.5, 3.0), 5),
                                       ((184, 59, 573, 0.5, 0.8),
                                        2**31 + 11)])
def test_bipartite_copy_is_the_programs(size, seed):
    want = G.bipartite_powerlaw(*size, seed=seed)
    got = bipartite.bipartite_powerlaw(*size, seed=seed)
    assert (got.inst.n, got.inst.s, got.inst.t) == (want.graph.n, want.s,
                                                    want.t)
    assert (got.n_left, got.n_right) == (want.n_left, want.n_right)
    np.testing.assert_array_equal(got.inst.edges, want.graph.edges)
    np.testing.assert_array_equal(got.inst.caps, want.graph.cap)
    np.testing.assert_array_equal(got.lr, want.lr_edges)


def small_run(seed=1234567890123, seconds=2.0):
    return run.run_cell(CELL, seed, seconds, False, chip=False,
                        config_overrides=dict(SMALL))


class _Bare:
    """Spans and counts of the benchmark's ``Run``, nothing else."""

    def __init__(self):
        self.spans, self.counts = {}, {}

    span = run.Run.span
    count = run.Run.count
    tracing = False


def test_matching_cell_rehearsal():
    res = small_run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 1
    assert res["window_compiles"] == 0
    e2e = {m["name"] for m in run.load_cell(CELL)["end_to_end"]}
    assert set(res["metrics"]) == e2e == {"solve_s", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert set(res["checks"]) == {"value_gap", "matching_faults", "cut_gap",
                                  "unanswered"}


@pytest.mark.parametrize("fault", [faults_matching.control, faults.unchanged,
                                   faults.altered])
def test_control_and_faults_are_caught(fault):
    """The control (pairs read from the preflow without phase 2), a step
    that returns its state unchanged, and a value off by one each make a
    small run not correct through ``run.run_cell``."""
    with fault():
        res = small_run(seed=987654321, seconds=1.0)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
    assert any(v["value"] > v["limit"] for v in res["checks"].values())
    if fault is faults_matching.control:
        assert res["checks"]["matching_faults"]["value"] > 0
        assert res["checks"]["value_gap"]["value"] == 0


def _drop_pair(a):
    a.pairs = a.pairs[1:]


def _match_twice(a):
    a.pairs = a.pairs.copy()
    a.pairs[1, 0] = a.pairs[0, 0]


def _not_a_member(a):
    members = set(map(tuple, a.bp.lr.tolist()))
    u = int(a.pairs[0, 0])
    free = next(v for v in range(a.bp.n_left, a.bp.n_left + a.bp.n_right)
                if (u, v) not in members)
    a.pairs = a.pairs.copy()
    a.pairs[0, 1] = free


def _wrong_value(a):
    a.value += 1


@pytest.mark.parametrize("plant", [_drop_pair, _match_twice, _not_a_member,
                                   _wrong_value])
def test_planted_fault_is_caught(plant):
    """Each fault planted in one answer of a sound run makes the
    comparison come out not correct, by at least one check."""
    drv = _driver()
    cfg = {**run.load_cell(CELL)["config"], **SMALL}
    bp = drv.make_graph(cfg, 77)

    answers = [drv.cold_solve(_Bare(), Solver(), bp) for _ in range(2)]
    checks, _, failed = drv.compare_answers(answers)
    assert failed == 0 and all(v["value"] == 0 for v in checks.values())
    answers[1] = dataclasses.replace(answers[1])
    plant(answers[1])
    checks, _, failed = drv.compare_answers(answers)
    assert failed == 1
    assert any(v["value"] > v["limit"] for v in checks.values()), checks


def test_isomorph_keeps_the_shapes():
    """The comparison's graph has the timed graph's vertex count, arc
    count and largest degree, so it runs on the window's programs, but
    other vertex ids and closed source and sink arcs: its maximum
    matching falls below both sides' open vertices, so a cut of every
    open source arc or of every open sink arc fails ``cut_gap``."""
    drv = _driver()
    cfg = {**run.load_cell(CELL)["config"], **SMALL}
    timed = drv.make_graph(cfg, 9)
    iso = drv.check_graph(cfg, 9)
    assert not np.array_equal(iso.lr, timed.lr)
    a = build_residual(facade.program_graph(timed.inst), "bcsr")
    b = build_residual(facade.program_graph(iso.inst), "bcsr")
    assert (a.n, a.num_arcs, a.deg_max) == (b.n, b.num_arcs, b.deg_max)
    np.testing.assert_array_equal(np.sort(np.diff(a.indptr)),
                                  np.sort(np.diff(b.indptr)))

    inst = iso.inst
    ref = reference_matching.matching_value(iso)
    assert ref < reference_matching.matching_value(timed)
    for end, col in ((inst.s, 0), (inst.t, 1)):
        arcs = inst.edges[:, col] == end
        assert 0 < np.count_nonzero(inst.caps[arcs] == 0) < arcs.sum()
        assert ref < int(inst.caps[arcs].sum())
    only_s = np.zeros(inst.n, bool)
    only_s[inst.s] = True
    assert reference.cut_gap(inst, ref, only_s) > 0
    assert reference.cut_gap(inst, ref, np.arange(inst.n) != inst.t) > 0
    # the program's matching of it is the reference's and certified
    ans = drv.cold_solve(_Bare(), Solver(), iso)
    checks, _, failed = drv.compare_answers([ans])
    assert failed == 0 and ans.value == ref, checks


def test_cell_graph_size_and_value():
    """The cell's graph is its published shape cut by 8 on every scale
    key, with the size and matching recorded for it, and every seed's
    edge order gives the program the same residual."""
    cfg = run.load_cell(CELL)["config"]
    for key in ("n_left", "n_right", "n_edges"):
        assert round(cfg["published"][key] / 8) == cfg[key], key
    assert set(cfg["reduced"]) == {"n_left", "n_right", "n_edges"}
    drv = _driver()
    bp = drv.make_graph(cfg, 1)
    want = build_residual(facade.program_graph(bp.inst), "bcsr")
    assert (want.n, want.num_arcs) == (15543, 104422)
    assert reference_matching.matching_value(bp) == 3761
    got = build_residual(facade.program_graph(
        drv.make_graph(cfg, 2**31 + 7).inst), "bcsr")
    for field in ("indptr", "heads", "rev", "res0"):
        np.testing.assert_array_equal(getattr(got, field),
                                      getattr(want, field))


def test_cell_graph_hubs_as_recorded():
    """The largest user's and the largest group's memberships in the
    timed graph are those the configuration records beside its assumed
    exponents."""
    cfg = run.load_cell(CELL)["config"]
    bp = _driver().make_graph(cfg, 3)
    L = bp.n_left
    assert np.bincount(bp.lr[:, 0], minlength=L).max() \
        == cfg["max_degree_left"]
    assert np.bincount(bp.lr[:, 1] - L).max() == cfg["max_degree_right"]
