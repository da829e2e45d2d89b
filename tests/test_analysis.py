"""The static-analysis subsystem: IR census, every contract rule
(positive fixture + seeded violation each), the AST lint, the HLO
backend, the surface registry, and the analyzer entry point.

Every rule gets BOTH directions: a clean program that must pass and a
deliberately broken one that must fire — a rule that never fires is
worse than no rule, because it reads as a guarantee.
"""
import textwrap

import jax
import jax.experimental
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ir
from repro.analysis.hlo import ReplicaGroupParseError, collective_bytes
from repro.analysis.lint import lint_file, run_lint
from repro.analysis.rules import (
    Int32Lattice,
    LaunchBudget,
    NoHostSync,
    NoVmappedPallasCall,
    ScanChunkShape,
    TraceBudget,
    check_rules,
)
from repro.core import engine

# ---------------------------------------------------------------------------
# fixtures: tiny traced programs, clean and deliberately broken
# ---------------------------------------------------------------------------


def _tiny_pallas(x):
    """One native pallas_call launch (the clean shape)."""
    import jax.experimental.pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True)(x)


def _engine_loop(x):
    """The blessed steady-state shape: one while over one scanned chunk."""
    return engine.run_bulk_loop(lambda c: c + 1, x,
                                cond_fn=lambda c: c < 10, chunk=4)


# ---------------------------------------------------------------------------
# the IR walker
# ---------------------------------------------------------------------------


def test_count_eqns_descends_scan_bodies():
    def f(x):
        def body(c, _):
            return c + jnp.sin(c), None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    jaxpr = jax.make_jaxpr(f)(jnp.float32(1.0))
    assert ir.count_eqns(jaxpr,
                         lambda e: e.primitive.name == "sin") == 1


def test_count_eqns_descends_cond_branches():
    # cond keeps its branches in a tuple param — the historical per-test
    # walkers missed those entirely
    def f(x):
        return jax.lax.cond(x > 0, lambda v: jnp.sin(v),
                            lambda v: jnp.cos(v), x)

    jaxpr = jax.make_jaxpr(f)(jnp.float32(1.0))
    names = {"sin", "cos"}
    assert ir.count_eqns(jaxpr,
                         lambda e: e.primitive.name in names) == 2


def test_census_pallas_launch_and_kernel_body_split():
    x = jnp.zeros((8,), jnp.int32)
    census = ir.census(_tiny_pallas, x)
    assert census.pallas_call_count == 1
    launch = census.pallas_calls[0]
    assert not launch.vmapped
    # kernel-body eqns are accounted separately, never in eqn_count
    assert census.kernel_eqn_count >= 1
    assert census.count("pallas_call") == 1


def test_census_dead_carry_detection():
    def f(x):
        # second carry leaf is threaded but its final value is unused
        a, _ = jax.lax.while_loop(lambda c: c[0] < 10,
                                  lambda c: (c[0] + 1, c[1] * 2), (x, x))
        return a

    census = ir.census(f, jnp.int32(0))
    assert census.dead_carry_leaves == 1


def test_loop_counts_shape():
    lc = ir.loop_counts(_engine_loop, jnp.int32(0))
    assert (lc.while_, lc.scan, lc.pallas) == (1, 1, 0)
    assert tuple(lc) == (1, 1, 0)


# ---------------------------------------------------------------------------
# rule: NoVmappedPallasCall
# ---------------------------------------------------------------------------


def test_no_vmapped_pallas_call_passes_native_launch():
    census = ir.census(_tiny_pallas, jnp.zeros((8,), jnp.int32))
    assert check_rules(census, [NoVmappedPallasCall()]) == []


def test_no_vmapped_pallas_call_fires_on_vmap():
    census = ir.census(jax.vmap(_tiny_pallas),
                       jnp.zeros((3, 8), jnp.int32))
    out = check_rules(census, [NoVmappedPallasCall()], "fixture")
    assert len(out) == 1
    assert out[0].rule == "no-vmapped-pallas-call"
    assert "vmap-batched" in out[0].message


# ---------------------------------------------------------------------------
# rule: LaunchBudget
# ---------------------------------------------------------------------------


def test_launch_budget_passes_within_budget():
    census = ir.census(_tiny_pallas, jnp.zeros((8,), jnp.int32))
    assert check_rules(census, [LaunchBudget(1)]) == []


def test_launch_budget_fires_over_budget():
    def two_launches(x):
        return _tiny_pallas(_tiny_pallas(x))

    census = ir.census(two_launches, jnp.zeros((8,), jnp.int32))
    out = check_rules(census, [LaunchBudget(1)], "fixture")
    assert [v.rule for v in out] == ["launch-budget"]
    assert "2 pallas_call launches" in out[0].message


# ---------------------------------------------------------------------------
# rule: NoHostSync
# ---------------------------------------------------------------------------


def test_no_host_sync_passes_clean_program():
    census = ir.census(_engine_loop, jnp.int32(0))
    assert check_rules(census, [NoHostSync()]) == []


def test_no_host_sync_fires_on_injected_io_callback():
    from jax.experimental import io_callback

    def bad(x):
        y = x + 1
        io_callback(lambda v: np.asarray(v),
                    jax.ShapeDtypeStruct((), jnp.int32), y)
        return y

    census = ir.census(bad, jnp.int32(0))
    out = check_rules(census, [NoHostSync()], "fixture")
    assert len(out) == 1
    assert out[0].rule == "no-host-sync"
    assert "io_callback" in out[0].message


def test_no_host_sync_allowlist():
    from jax.experimental import io_callback

    def logged(x):
        io_callback(lambda v: np.asarray(v),
                    jax.ShapeDtypeStruct((), jnp.int32), x)
        return x

    census = ir.census(logged, jnp.int32(0))
    assert check_rules(census, [NoHostSync(allow=("io_callback",))]) == []


def test_benign_constant_device_put_not_flagged():
    # jnp.asarray on a python scalar inside a traced body stages a
    # device_put of a Literal — constant placement, not a transfer
    def f(x):
        def body(c):
            return c + jnp.asarray(1, jnp.int32)
        return jax.lax.while_loop(lambda c: c < 10, body, x)

    census = ir.census(f, jnp.int32(0))
    assert check_rules(census, [NoHostSync()]) == []


# ---------------------------------------------------------------------------
# rule: ScanChunkShape
# ---------------------------------------------------------------------------


def test_scan_chunk_shape_passes_engine_loop():
    census = ir.census(_engine_loop, jnp.int32(0))
    assert check_rules(census, [ScanChunkShape(whiles=1, scans=1)]) == []


def test_scan_chunk_shape_fires_on_module_level_while_loop():
    # a bare while_loop shell riding alongside the engine's loop — the
    # exact duplication the engine port eliminated
    def bad(x):
        y = _engine_loop(x)
        return jax.lax.while_loop(lambda c: c < 20, lambda c: c + 1, y)

    census = ir.census(bad, jnp.int32(0))
    out = check_rules(census, [ScanChunkShape(whiles=1, scans=1)],
                      "fixture")
    assert any("expected 1 outer while" in v.message for v in out)


def test_scan_chunk_shape_fires_on_orphan_scan():
    # a scan with no enclosing while is a loop shell the engine does not
    # own — flagged even when the totals happen to match
    def bad(x):
        out, _ = jax.lax.scan(lambda c, _: (c + 1, None), x, None,
                              length=4)
        return jax.lax.while_loop(lambda c: c < 10, lambda c: c + 1, out)

    census = ir.census(bad, jnp.int32(0))
    out = check_rules(census, [ScanChunkShape(whiles=1, scans=1)],
                      "fixture")
    assert any("scan outside any while" in v.message for v in out)


# ---------------------------------------------------------------------------
# rule: Int32Lattice
# ---------------------------------------------------------------------------


def test_int32_lattice_passes_int32_program():
    census = ir.census(_engine_loop, jnp.int32(0))
    assert check_rules(census, [Int32Lattice()]) == []


def test_int32_lattice_fires_on_stray_int64_widening():
    with jax.enable_x64(True):
        def bad(x):
            return x.astype(jnp.int64) + 1

        census = ir.census(bad, jnp.zeros((4,), jnp.int32))
    out = check_rules(census, [Int32Lattice()], "fixture")
    assert len(out) == 1
    assert out[0].rule == "int32-lattice"
    assert "widening" in out[0].message
    assert "as_state_dtype" in out[0].message


def test_int32_lattice_fires_on_lossy_narrowing():
    def bad(x):
        return x.astype(jnp.int16)

    census = ir.census(bad, jnp.zeros((4,), jnp.int32))
    out = check_rules(census, [Int32Lattice()], "fixture")
    assert len(out) == 1
    assert "lossy narrowing" in out[0].message


def test_int32_lattice_exempts_bool_predicates():
    def predicated(x):
        return (x > 0).astype(jnp.int32)

    census = ir.census(predicated, jnp.zeros((4,), jnp.int32))
    assert check_rules(census, [Int32Lattice()]) == []


# ---------------------------------------------------------------------------
# rule: TraceBudget
# ---------------------------------------------------------------------------


def test_trace_budget_passes_under_ceiling():
    census = ir.census(_engine_loop, jnp.int32(0))
    assert check_rules(census, [TraceBudget(10_000)]) == []


def test_trace_budget_fires_over_ceiling():
    census = ir.census(_engine_loop, jnp.int32(0))
    out = check_rules(census, [TraceBudget(1)], "fixture")
    assert len(out) == 1
    assert out[0].rule == "trace-budget"


# ---------------------------------------------------------------------------
# the AST lint
# ---------------------------------------------------------------------------


def _lint_src(tmp_path, rel, body):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(body))
    return lint_file(path, tmp_path)


def test_lint_flags_loop_shell_outside_engine(tmp_path):
    out = _lint_src(tmp_path, "src/repro/core/foo.py", """\
        import jax

        def f(x):
            return jax.lax.while_loop(lambda c: c < 3, lambda c: c + 1, x)
    """)
    assert [f.rule for f in out] == ["loop-shell"]


def test_lint_allows_loop_shell_in_engine_and_out_of_scope(tmp_path):
    body = """\
        import jax

        def f(x):
            return jax.lax.scan(lambda c, _: (c, None), x, None, length=2)
    """
    assert _lint_src(tmp_path, "src/repro/core/engine.py", body) == []
    assert _lint_src(tmp_path, "src/repro/models/foo.py", body) == []


def test_lint_flags_hardcoded_interpret_true(tmp_path):
    out = _lint_src(tmp_path, "src/repro/kernels/foo.py", """\
        def f(kern, x):
            return kern(x, interpret=True)
    """)
    assert "interpret-literal" in [f.rule for f in out]


def test_lint_flags_host_sync_in_core(tmp_path):
    out = _lint_src(tmp_path, "src/repro/core/foo.py", """\
        import jax

        def f(x):
            return jax.device_get(x.block_until_ready())
    """)
    assert [f.rule for f in out] == ["host-sync", "host-sync"]


def test_lint_int64_state_cast_needs_narrowing_or_pragma(tmp_path):
    bare = """\
        import numpy as np

        def f(res):
            return np.asarray(res, np.int64).copy()
    """
    out = _lint_src(tmp_path, "src/repro/core/foo.py", bare)
    assert [f.rule for f in out] == ["int64-state-cast"]

    blessed = """\
        import numpy as np
        from repro.core.batched import as_state_dtype

        def f(res):
            wide = np.asarray(res, np.int64) * 2
            return as_state_dtype(wide, "res")
    """
    assert _lint_src(tmp_path, "src/repro/core/foo.py", blessed) == []

    pragma = """\
        import numpy as np

        def f(res):
            return np.asarray(res, np.int64)  # lint-ok: int64-state-cast
    """
    assert _lint_src(tmp_path, "src/repro/core/foo.py", pragma) == []


def test_lint_non_state_int64_cast_not_flagged(tmp_path):
    out = _lint_src(tmp_path, "src/repro/core/foo.py", """\
        import numpy as np

        def f(edges):
            return np.asarray(edges, np.int64)
    """)
    assert out == []


def test_lint_flags_bare_assert_in_library(tmp_path):
    out = _lint_src(tmp_path, "src/repro/core/foo.py", """\
        def f(x):
            assert x > 0
            assert x < 10, "messaged asserts are fine"
            return x
    """)
    assert [f.rule for f in out] == ["bare-assert"]
    assert out[0].line == 2


def test_lint_flags_private_walker_in_tests(tmp_path):
    out = _lint_src(tmp_path, "tests/test_foo.py", """\
        def count(jaxpr):
            return sum(1 for e in jaxpr.eqns)
    """)
    assert [f.rule for f in out] == ["private-walker"]


def test_repo_tree_is_lint_clean():
    """The acceptance gate: the actual repo holds every source-side
    invariant — including that no test file retains a private jaxpr
    walker."""
    findings = run_lint(".")
    assert not findings, "\n".join(map(str, findings))


# ---------------------------------------------------------------------------
# the HLO backend
# ---------------------------------------------------------------------------


def test_hlo_strict_raises_on_malformed_replica_groups():
    text = "  %ar = f32[64]{0} all-reduce(%x), no_groups_here=1\n"
    with pytest.raises(ReplicaGroupParseError) as exc:
        collective_bytes(text)
    assert "all-reduce" in str(exc.value)


def test_hlo_lenient_warns_and_assumes_two(recwarn):
    text = "  %ar = f32[64]{0} all-reduce(%x), no_groups_here=1\n"
    out = collective_bytes(text, strict=False)
    assert out["counts"] == {"all-reduce": 1}
    # 2 * bytes * (g-1)/g with the assumed g=2
    assert out["total_bytes"] == pytest.approx(2 * 64 * 4 * 0.5)
    assert any("UNDERCOUNT" in str(w.message) for w in recwarn.list)


def test_hlo_collective_permute_needs_no_groups():
    text = ("  %cp = f32[16]{0} collective-permute(%w), "
            "source_target_pairs={{0,1}}\n")
    out = collective_bytes(text)  # strict: must not raise
    assert out["total_bytes"] == 16 * 4


# ---------------------------------------------------------------------------
# surfaces + baselines + the analyzer entry point
# ---------------------------------------------------------------------------


def test_surface_registry_enumerates_every_family():
    from repro.analysis import surfaces as S

    names = [s.name for s in S.iter_surfaces()]
    assert len(names) == len(set(names))
    families = {s.family for s in S.iter_surfaces()}
    assert families == {"run_cycles", "batched_run_cycles",
                        "global_relabel", "phase2", "streaming",
                        "distributed"}
    # modes x layouts: bsearch only has the bcsr layout
    assert "run_cycles/vc_kernel_bsearch/bcsr" in names
    assert "run_cycles/vc_kernel_bsearch/rcsr" not in names


def test_global_relabel_surfaces_hold_their_contracts():
    # one cheap family end-to-end (the full sweep is the CI analyze job)
    from repro.analysis import surfaces as S

    for surf in S.iter_surfaces():
        if surf.family != "global_relabel":
            continue
        census, violations = S.analyze_surface(surf)
        assert violations == [], (surf.name, violations)
        expected_pallas = 1 if surf.tag_dict()["kernel"] == "True" else 0
        assert census.loop_counts() == (1, 1, expected_pallas)


def test_scan_chunk_baselines_prove_engine_saving():
    from repro.analysis.baselines import scan_chunk_baselines

    base = scan_chunk_baselines()
    assert set(base) == {"vc", "tc", "vc_kernel", "vc_kernel_bsearch"}
    for mode, rec in base.items():
        assert rec["scanned_eqns"] < rec["unrolled_eqns"], mode


def test_mode_baselines_prefers_analysis_json(tmp_path):
    import json

    from repro.analysis.baselines import mode_baselines

    path = tmp_path / "ANALYSIS.json"
    canned = {"vc": {"scan_chunk": 4, "scanned_eqns": 10,
                     "unrolled_eqns": 40}}
    path.write_text(json.dumps({"baselines": canned}))
    assert mode_baselines(path) == canned
    # absent file -> computed fresh (and cached)
    assert "vc" in mode_baselines(tmp_path / "missing.json")


def test_run_analysis_payload_shape(tmp_path):
    from repro.launch.analyze import run_analysis

    payload = run_analysis(patterns=["global_relabel/single*"],
                           with_lint=False, with_baselines=False)
    assert payload["summary"]["rule_violations"] == 0
    assert set(payload["surfaces"]) == {"global_relabel/single",
                                        "global_relabel/single/kernel"}
    rec = payload["surfaces"]["global_relabel/single/kernel"]
    assert rec["ok"] and rec["census"]["loop_shape"]["pallas_call"] == 1
    assert rec["census"]["pallas_calls"][0]["vmapped_dims"] == []
