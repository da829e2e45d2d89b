"""Overload hardening: admission control, deadlines, the degradation
ladder, handle validation/quarantine, and end-to-end chaos correctness.

Every test drives the REAL service against injected faults
(``repro.runtime.fault.FaultPlan``) — nothing is mocked — and the
terminal assertion is always the same: admitted requests return the
exact max-flow, everything else fails with a typed error.
"""
import time

import numpy as np
import pytest

from repro.api import MaxflowProblem, Solver
from repro.core.csr import Graph
from repro.core.ref_maxflow import dinic_maxflow
from repro.errors import (BudgetExhausted, DeadlineExceeded, DispatchFailed,
                          HandleCorrupted, Overloaded, ServiceError)
from repro.graphs import generators as G
from repro.runtime.fault import CORRUPTION_KINDS, FaultPlan, InjectedFault
from repro.serving import MaxflowService, ServiceConfig
from repro.serving.policy import (HOST_REF, LADDER, BucketLadder,
                                  demote_mode, ladder_rank)
from repro.serving.workload import arrival_times, drive, resolve_item, \
    synthesize


def _want(g, s, t):
    return Solver().solve(MaxflowProblem(g, s, t)).value


def _svc(faults=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("cycle_chunk", 16)
    kw.setdefault("mode", "vc")
    kw.setdefault("retry_base_s", 0.0)  # tests don't need real sleeps
    return MaxflowService(ServiceConfig(**kw), faults=faults)


def _graphs(n_graphs, seed0=0):
    return [G.random_sparse(40, 160, seed=seed0 + i) for i in
            range(n_graphs)]


# -- admission control ---------------------------------------------------


def test_queue_overflow_rejects_typed():
    svc = _svc(max_queue=3, max_batch=8)
    admitted, rejected = 0, 0
    for g, s, t in _graphs(8):
        try:
            svc.submit(g, s, t)
            admitted += 1
        except Overloaded as exc:
            rejected += 1
            assert exc.limit == 3
            assert exc.depth >= 3
            assert exc.retry_after_s > 0
            d = exc.details()
            assert set(d) >= {"bucket", "depth", "limit", "retry_after_s"}
    assert admitted == 3 and rejected == 5
    assert svc.stats()["robustness"]["rejected"] == 5
    # draining the queue re-opens admission
    assert svc.flush() == 3
    g, s, t = G.random_sparse(40, 160, seed=99)
    assert svc.submit(g, s, t).result().maxflow == _want(g, s, t)


def test_unbounded_queue_never_rejects():
    svc = _svc(max_queue=None, max_batch=8)
    futs = [svc.submit(g, s, t) for g, s, t in _graphs(8)]
    svc.flush()
    assert all(f.result().maxflow >= 0 for f in futs)
    assert svc.stats()["robustness"]["rejected"] == 0


def test_overload_sheds_expired_before_rejecting():
    # a queue full of EXPIRED work must admit fresh requests, not reject
    svc = _svc(max_queue=2, max_batch=8)
    g1, s1, t1 = G.random_sparse(40, 160, seed=0)
    g2, s2, t2 = G.random_sparse(40, 160, seed=1)
    f1 = svc.submit(g1, s1, t1, deadline_s=1e-6)
    f2 = svc.submit(g2, s2, t2, deadline_s=1e-6)
    time.sleep(0.005)  # both now expired
    g3, s3, t3 = G.random_sparse(40, 160, seed=2)
    f3 = svc.submit(g3, s3, t3)  # admission sheds the dead pair
    svc.flush()
    for f in (f1, f2):
        with pytest.raises(DeadlineExceeded):
            f.result()
    assert f3.result().maxflow == _want(g3, s3, t3)
    rb = svc.stats()["robustness"]
    assert rb["shed"] == 2 and rb["rejected"] == 0


# -- deadlines -----------------------------------------------------------


def test_deadline_expired_at_admission():
    svc = _svc()
    g, s, t = G.random_sparse(40, 160, seed=0)
    with pytest.raises(DeadlineExceeded) as ei:
        svc.submit(g, s, t, deadline_s=0.0)
    assert ei.value.where == "admission"
    assert svc.stats()["robustness"]["expired_at_admission"] == 1


def test_deadline_expiry_ordering():
    """Expired requests are shed BEFORE dispatch; live ones in the same
    bucket still solve — the shed work never pays for (or rides in) a
    batch."""
    svc = _svc(max_batch=8)
    g1, s1, t1 = G.random_sparse(40, 160, seed=0)
    g2, s2, t2 = G.random_sparse(40, 160, seed=1)
    f_dead = svc.submit(g1, s1, t1, deadline_s=1e-6)
    f_live = svc.submit(g2, s2, t2, deadline_s=60.0)
    time.sleep(0.005)
    solved = svc.flush()
    assert solved == 1  # only the live one dispatched
    with pytest.raises(DeadlineExceeded) as ei:
        f_dead.result()
    assert ei.value.where == "queue"
    assert ei.value.waited_s >= ei.value.deadline_s
    assert f_live.result().maxflow == _want(g2, s2, t2)
    assert svc.stats()["robustness"]["shed"] == 1


def test_poll_sheds_without_flushing():
    # poll() must surface expiry even when no bucket is due
    svc = _svc(max_batch=8)
    g, s, t = G.random_sparse(40, 160, seed=0)
    fut = svc.submit(g, s, t, deadline_s=1e-6)
    time.sleep(0.005)
    assert svc.poll() == 0  # nothing solved...
    assert fut.done()  # ...but the expired request already failed
    with pytest.raises(DeadlineExceeded):
        fut.result()


def test_deadline_pressure_flushes_early():
    # a near-deadline request makes its bucket ready before max_batch
    svc = _svc(max_batch=8, deadline_slack_s=60.0)
    g, s, t = G.random_sparse(40, 160, seed=0)
    fut = svc.submit(g, s, t, deadline_s=5.0)  # within slack immediately
    assert svc.poll() == 1
    assert fut.result().maxflow == _want(g, s, t)


def test_future_exception_api():
    svc = _svc()
    g, s, t = G.random_sparse(40, 160, seed=0)
    fut = svc.submit(g, s, t, deadline_s=1e-6)
    time.sleep(0.005)
    svc.poll()
    exc = fut.exception()
    assert isinstance(exc, DeadlineExceeded)
    ok = svc.submit(*G.random_sparse(40, 160, seed=1))
    svc.flush()
    assert ok.exception() is None


# -- retry / backoff -----------------------------------------------------


def test_transient_fault_retried_same_mode():
    # one injected failure, then clean: the retry succeeds WITHOUT
    # demoting (fail_mode_limit bounds the injection)
    plan = FaultPlan(seed=0, fail_modes=("vc",), fail_mode_rate=1.0,
                     fail_mode_limit=1)
    svc = _svc(faults=plan, retry_limit=2)
    g, s, t = G.random_sparse(40, 160, seed=0)
    fut = svc.submit(g, s, t)
    svc.flush()
    assert fut.result().maxflow == _want(g, s, t)
    rb = svc.stats()["robustness"]
    assert rb["retries"] == 1
    assert rb["transient_demotions"] == 0
    assert rb["host_fallbacks"] == 0
    assert plan.stats()["mode_failures"] == 1


def test_backoff_schedule_exponential_jittered():
    svc = _svc(retry_base_s=0.01, retry_max_s=0.5, retry_seed=7)
    delays = [svc._backoff_s(a) for a in range(6)]
    # jitter keeps every delay within [0.5, 1.0) x the deterministic curve
    for a, d in enumerate(delays):
        ceiling = min(0.01 * 2 ** a, 0.5)
        assert 0.5 * ceiling <= d < ceiling
    # the cap binds eventually
    assert max(delays) < 0.5
    # seeded rng -> reproducible schedule
    svc2 = _svc(retry_base_s=0.01, retry_max_s=0.5, retry_seed=7)
    assert [svc2._backoff_s(a) for a in range(6)] == delays


def test_retry_limit_zero_demotes_immediately():
    plan = FaultPlan(seed=0, fail_modes=("vc",), fail_mode_rate=1.0,
                     fail_mode_limit=1)
    svc = _svc(faults=plan, retry_limit=0)
    g, s, t = G.random_sparse(40, 160, seed=0)
    fut = svc.submit(g, s, t)
    svc.flush()
    # vc failed once -> demoted straight to host_ref, still correct
    assert fut.result().maxflow == _want(g, s, t)
    rb = svc.stats()["robustness"]
    assert rb["retries"] == 0
    assert rb["transient_demotions"] == 1
    assert rb["host_fallbacks"] == 1


# -- degradation ladder --------------------------------------------------


def test_ladder_order_and_demote():
    assert LADDER[-1] == HOST_REF
    assert demote_mode("vc_kernel_bsearch") == "vc_kernel"
    assert demote_mode("vc") == HOST_REF
    assert demote_mode(HOST_REF) is None
    assert ladder_rank("tc") == ladder_rank("vc")
    ranks = [ladder_rank(m) for m in LADDER]
    assert ranks == sorted(ranks)


def test_bucket_ladder_sticky_ceiling():
    lad = BucketLadder(demote_after=2)
    top = "vc_kernel_bsearch"
    assert lad.clamp(top) == top
    lad.note_failure(top)
    assert lad.clamp(top) == top  # one strike: transient
    lad.note_failure(top)
    assert lad.clamp(top) == "vc_kernel"  # two: sticky
    assert lad.demotions == 1
    assert lad.clamp("vc") == "vc"  # modes below the ceiling unaffected
    assert lad.clamp(HOST_REF) == HOST_REF


def test_mode_demotion_end_to_end():
    """Persistent vc_kernel_bsearch failures walk the flush down the
    ladder to a working mode; the sticky ceiling spares later flushes the
    re-walk."""
    plan = FaultPlan(seed=0, fail_modes=("vc_kernel_bsearch",),
                     fail_mode_rate=1.0)
    svc = _svc(faults=plan, mode="vc_kernel_bsearch", retry_limit=1,
               demote_after=1, max_batch=2)
    g, s, t = G.random_sparse(40, 160, seed=0)
    fut = svc.submit(g, s, t)
    svc.flush()
    assert fut.result().maxflow == _want(g, s, t)
    rb = svc.stats()["robustness"]
    assert rb["transient_demotions"] >= 1
    assert rb["sticky_demotions"] == 1
    failures_after_first = plan.stats()["mode_failures"]
    # second flush starts below vc_kernel_bsearch: no new injections
    g2, s2, t2 = G.random_sparse(40, 160, seed=1)
    fut2 = svc.submit(g2, s2, t2)
    svc.flush()
    assert fut2.result().maxflow == _want(g2, s2, t2)
    assert plan.stats()["mode_failures"] == failures_after_first
    lads = rb["ladders"]
    assert any(e["ceiling_mode"] != "vc_kernel_bsearch"
               for e in lads.values())


def test_every_rung_fails_is_typed_terminal():
    plan = FaultPlan(seed=0, fail_mode_rate=1.0,
                     fail_modes=("vc", "tc", "vc_kernel",
                                 "vc_kernel_bsearch", HOST_REF))
    svc = _svc(faults=plan, retry_limit=0)
    g, s, t = G.random_sparse(40, 160, seed=0)
    fut = svc.submit(g, s, t)
    svc.flush()
    with pytest.raises(DispatchFailed) as ei:
        fut.result()
    assert ei.value.attempts >= 2
    assert "InjectedFault" in ei.value.cause
    assert svc.stats()["robustness"]["dispatch_failed"] == 1


def test_budget_exhaustion_typed():
    # a budget too small to converge raises a typed BudgetExhausted
    # carrying the spend — and it still subclasses RuntimeError, so
    # pre-taxonomy ``except RuntimeError`` callers keep working
    g, s, t = G.random_sparse(40, 160, seed=0)
    with pytest.raises(BudgetExhausted) as ei:
        Solver(mode="vc", max_cycles=1,
               global_relabel_cadence=1).solve(MaxflowProblem(g, s, t))
    assert isinstance(ei.value, RuntimeError)  # legacy catch compat
    assert isinstance(ei.value, ServiceError)
    assert ei.value.cycles_spent >= 1 and ei.value.limit == 1
    assert ei.value.partial
    d = ei.value.details()
    assert d["cycles_spent"] == ei.value.cycles_spent
    assert d["partial"] is True


# -- handle validation / quarantine --------------------------------------


@pytest.mark.parametrize("kind", CORRUPTION_KINDS)
def test_validate_catches_every_corruption_kind(kind):
    g, s, t = G.random_sparse(40, 160, seed=0)
    sol = Solver(mode="vc").solve(MaxflowProblem(g, s, t))
    h = sol.warm_start
    h.validate()  # pristine: passes
    plan = FaultPlan(seed=3, corrupt_handle_rate=1.0)
    plan.injected["corruptions"] = CORRUPTION_KINDS.index(kind)
    assert plan.corrupt_handle(h) == kind
    with pytest.raises(HandleCorrupted) as ei:
        h.validate()
    assert ei.value.reasons


def test_quarantine_on_resubmit():
    """A poisoned cached handle is quarantined at reuse: the resubmit
    still returns the exact answer of the edited graph (rebuilt cold,
    never warm-started from garbage)."""
    plan = FaultPlan(seed=3, corrupt_handle_rate=1.0)
    svc = _svc(faults=plan, max_batch=2)
    g, s, t = G.random_sparse(40, 160, seed=5)
    base = svc.submit(g, s, t)
    svc.flush()
    base_res = base.result()
    assert base_res.maxflow == _want(g, s, t)  # answer predates poison
    assert plan.stats()["corruptions"] >= 1
    u, v = int(g.edges[0][0]), int(g.edges[0][1])
    fut = svc.resubmit(base_res.graph_id, [(u, v, 3)])
    svc.flush()
    cap2 = g.cap.copy()
    cap2[0] += 3
    want = _want(Graph(g.n, g.edges, cap2), s, t)
    assert fut.result().maxflow == want
    assert svc.stats()["robustness"]["quarantined"] >= 1


def test_quarantine_on_stream_apply():
    plan = FaultPlan(seed=3, corrupt_handle_rate=1.0)
    svc = _svc(faults=plan, max_batch=2)
    g, s, t = G.random_sparse(40, 160, seed=6)
    sid = svc.open_stream(g, s, t)
    u, v = int(g.edges[0][0]), int(g.edges[0][1])
    fut = svc.stream_apply(sid, [(u, v, +4)])
    svc.flush()
    cap2 = g.cap.copy()
    cap2[0] += 4
    assert fut.result().maxflow == _want(Graph(g.n, g.edges, cap2), s, t)
    assert svc.stats()["robustness"]["quarantined"] >= 1


def test_validation_off_is_escape_hatch():
    # validate_handles=False restores the trusting fast path
    svc = _svc(validate_handles=False)
    g, s, t = G.random_sparse(40, 160, seed=0)
    base = svc.submit(g, s, t)
    svc.flush()
    u, v = int(g.edges[0][0]), int(g.edges[0][1])
    fut = svc.resubmit(base.result().graph_id, [(u, v, 2)])
    svc.flush()
    cap2 = g.cap.copy()
    cap2[0] += 2
    assert fut.result().maxflow == _want(Graph(g.n, g.edges, cap2), s, t)
    assert svc.stats()["robustness"]["quarantined"] == 0


# -- workload traces -----------------------------------------------------


@pytest.mark.parametrize("process", ["poisson", "bursty", "diurnal",
                                     "flood"])
def test_arrival_traces_deterministic_and_monotone(process):
    a = arrival_times(64, rate_hz=200.0, process=process, seed=11)
    b = arrival_times(64, rate_hz=200.0, process=process, seed=11)
    c = arrival_times(64, rate_hz=200.0, process=process, seed=12)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert len(a) == 64
    assert (np.diff(a) >= 0).all()
    if process == "flood":
        assert a[-1] <= 1e-3  # everything lands at once
    else:
        assert a[-1] > 0.01


def test_synthesize_content_identical_across_processes():
    # the arrival shape must not change WHICH graphs are generated
    flood = synthesize(32, seed=4, process="flood")
    pois = synthesize(32, seed=4, process="poisson")
    assert [it.kind for it in flood] == [it.kind for it in pois]
    for a, b in zip(flood, pois):
        if a.kind == "maxflow":
            assert np.array_equal(a.graph.edges, b.graph.edges)
    assert flood[-1].arrival_s < pois[-1].arrival_s


def test_unknown_process_rejected():
    with pytest.raises(ValueError, match="unknown arrival process"):
        arrival_times(4, process="tsunami")


# -- end-to-end chaos ----------------------------------------------------


def test_chaos_workload_no_wrong_answers():
    """The headline robustness property, end to end: flood arrivals,
    bounded queues, deadlines, injected dispatch faults AND handle
    corruption — every admitted request that completes returns the exact
    max-flow; every failure is typed."""
    items = synthesize(24, seed=2, process="flood", deadline_s=30.0)
    plan = FaultPlan(seed=2, dispatch_error_rate=0.3,
                     corrupt_handle_rate=1.0)
    svc = _svc(faults=plan, max_queue=6, retry_limit=2)
    records = drive(svc, items, poll_every=4)
    ok = err = 0
    for item, rec in zip(items, records):
        if rec["error"] is not None:
            assert isinstance(rec["error"], ServiceError)
            err += 1
            continue
        g, s, t = resolve_item(items, item)
        assert rec["result"].maxflow == dinic_maxflow(g, s, t), item.kind
        ok += 1
    assert ok > 0
    assert ok + err == len(items)
    rb = svc.stats()["robustness"]
    snap = svc.telemetry_snapshot()  # robustness section is JSON-clean
    assert snap["stats"]["robustness"]["retries"] == rb["retries"]


def test_chaos_deterministic_replay():
    # same seeds -> identical injection counts and identical outcomes
    def once():
        items = synthesize(16, seed=8, process="bursty", deadline_s=30.0)
        plan = FaultPlan(seed=8, dispatch_error_rate=0.4)
        svc = _svc(faults=plan, retry_limit=2, retry_seed=8)
        records = drive(svc, items, poll_every=3)
        vals = [r["result"].maxflow if r["error"] is None else
                type(r["error"]).__name__ for r in records]
        return vals, plan.stats()
    v1, s1 = once()
    v2, s2 = once()
    assert v1 == v2 and s1 == s2


def test_drive_resubmit_falls_back_when_base_failed():
    # base rejected at admission -> its resubmit cold-solves the edited
    # graph instead of erroring the whole drive
    items = synthesize(20, seed=3, process="flood")
    svc = _svc(max_queue=2, max_batch=8)
    records = drive(svc, items, poll_every=50)  # never poll mid-drive
    resub = [r for it, r in zip(items, records) if it.kind == "resubmit"]
    rejected = [r for r in records if isinstance(r["error"], Overloaded)]
    assert rejected, "flood against max_queue=2 must reject something"
    for it, rec in zip(items, records):
        if rec["error"] is None:
            g, s, t = resolve_item(items, it)
            assert rec["result"].maxflow == dinic_maxflow(g, s, t)
    assert any(r["error"] is None for r in resub) or not resub
