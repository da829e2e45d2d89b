"""CPU rehearsal of the benchmark: the copied generators give the
program's instances, and every cell's set-up, window and comparison run
end to end at a small size (the look for a chip skipped)."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import facade
import generators
import reference
import run

ROOT = Path(__file__).resolve().parents[2]

#: small sizes of each cell for the CPU
SMALL = {"rlg.cold": {"rows": 64, "cols": 4}}


def small_run(cell, seed=1234567890123, seconds=2.0):
    return run.run_cell(cell, seed, seconds, False, chip=False,
                        config_overrides=dict(SMALL[cell]))


@pytest.mark.parametrize("rows,cols,seed", [(8, 3, 0), (16, 5, 7),
                                            (32, 4, 2**31 + 5)])
def test_washington_copy_is_the_programs(rows, cols, seed):
    from repro.graphs import generators as G

    g, s, t = G.washington_rlg(rows, cols, seed=seed)
    inst = generators.washington_rlg(rows, cols, seed=seed)
    assert (inst.n, inst.s, inst.t) == (g.n, s, t)
    np.testing.assert_array_equal(inst.edges, g.edges)
    np.testing.assert_array_equal(inst.caps, g.cap)


def test_washington_4096x8_value():
    """The cell's network has the size and value recorded for it, and
    every seed's edge order gives the program the same residual."""
    from repro.core.csr import build_residual

    inst = generators.washington_rlg(4096, 8, seed=0)
    assert (inst.n, inst.edges.shape[0]) == (32770, 94208)
    assert reference.max_flow_value(inst) == 336130
    cfg = run.load_cell("rlg.cold")["config"]
    want = build_residual(facade.program_graph(inst), "bcsr")
    for seed in (1, 2**31 + 7):
        other = facade.make_instance(cfg, seed)
        assert not np.array_equal(other.edges, inst.edges)
        assert reference.max_flow_value(other) == 336130
        got = build_residual(facade.program_graph(other), "bcsr")
        for field in ("indptr", "heads", "rev", "res0"):
            np.testing.assert_array_equal(getattr(got, field),
                                          getattr(want, field))


def test_check_instance_draws_capacities_on_the_same_arcs():
    """The comparison's instance keeps the timed network's arcs, so the
    program builds the same residual structure and runs the same compiled
    programs; only the capacities, drawn from the seed, differ."""
    from repro.core.csr import build_residual

    cfg = {**run.load_cell("rlg.cold")["config"], "rows": 64, "cols": 4}
    timed = build_residual(facade.program_graph(
        facade.make_instance(cfg, 0)), "bcsr")
    caps = []
    for seed in (3, 3, 2**31 + 9):
        inst = facade.check_instance(cfg, seed)
        got = build_residual(facade.program_graph(inst), "bcsr")
        for field in ("indptr", "heads", "rev"):
            np.testing.assert_array_equal(getattr(got, field),
                                          getattr(timed, field))
        terminal = (inst.edges[:, 0] == inst.s) | (inst.edges[:, 1] == inst.t)
        assert inst.caps.min() >= 1
        assert inst.caps[~terminal].max() <= cfg["max_cap"]
        assert (inst.caps[terminal] % cfg["rows"] == 0).all()
        caps.append(inst.caps)
    np.testing.assert_array_equal(caps[0], caps[1])
    assert not np.array_equal(caps[0], caps[2])


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_cell_rehearsal(cell):
    res = small_run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["window_compiles"] == 0
    e2e = {m["name"] for m in run.load_cell(cell)["end_to_end"]}
    assert set(res["metrics"]) == e2e
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_no_result_off_tpu(tmp_path):
    """Off a TPU, or without the program beside it, the command exits
    non-zero and prints no result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cmd = [sys.executable, "bench/run.py", "--workload", "rlg.cold",
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    out = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
    bare = tmp_path / "bare"
    (bare / "bench").mkdir(parents=True)
    (bare / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json")
                                         .read_text())
    subprocess.run(["cp", "-r", str(ROOT / "bench"), str(bare)], check=True)
    out = subprocess.run(cmd, cwd=bare, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
