"""Launcher/dry-run machinery: spec trees, sharding rules, HLO parsing,
and a full (reduced-config) lower+compile on a 1x1 mesh."""
import dataclasses
import json

import jax
from repro import compat
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.launch import hlo_analysis as H
from repro.launch import shapes as SH
from repro.launch import specs as S
from repro.models import transformer as T
from repro.sharding import rules as SR


def _tiny_mesh():
    return compat.make_mesh((1, 1), ("data", "model"))


def test_spec_for_divisibility_guard():
    mesh = _tiny_mesh()
    # 'model' axis size 1 divides everything -> sharded specs collapse to None
    assert SR.spec_for(mesh, ("heads",), (7,)) == P("model")
    mesh16 = jax.sharding.Mesh(
        np.array(jax.devices() * 1).reshape(1, 1), ("data", "model"))
    assert SR.spec_for(mesh16, ("heads",), (8,)) is not None


def test_cells_for_skips_long_context_for_full_attention():
    dense = registry.get_config("qwen2-72b")
    names = [c.name for c in SH.cells_for(dense)]
    assert "long_500k" not in names and len(names) == 3
    for arch in ("mixtral-8x7b", "jamba-1.5-large-398b", "rwkv6-1.6b"):
        cfg = registry.get_config(arch)
        assert "long_500k" in [c.name for c in SH.cells_for(cfg)]


def test_input_specs_no_allocation():
    mesh = _tiny_mesh()
    cfg = registry.get_smoke_config("qwen3-4b")
    for cell in SH.cells_for(registry.get_config("qwen3-4b"))[:1]:
        cell = dataclasses.replace(cell, batch=2, seq=32)
        args, kind = S.input_specs(cfg, cell, mesh)
        for leaf in jax.tree.leaves(args):
            assert isinstance(leaf, jax.ShapeDtypeStruct)


@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_lower_compile_smoke_config(kind):
    """The dry-run path end-to-end on the reduced config, 1x1 mesh."""
    from repro.training import optimizer as O
    from repro.training.train_step import (make_decode_step,
                                           make_prefill_step,
                                           make_train_step)
    mesh = _tiny_mesh()
    cfg = registry.get_smoke_config("mixtral-8x7b")
    cell = dataclasses.replace(SH.LM_SHAPES["train_4k"], kind=kind,
                               batch=2, seq=64)
    args, _ = S.input_specs(cfg, cell, mesh)
    if kind == "train":
        fn = make_train_step(cfg, O.make_optimizer("adamw"))
    elif kind == "prefill":
        fn = make_prefill_step(cfg)
    else:
        fn = make_decode_step(cfg)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.cost_analysis()["flops"] > 0


def test_hlo_collective_parsing():
    text = """
  %all-gather = f32[64,32]{1,0} all-gather(%x), replica_groups=[4,2]<=[8]T(1,0), dimensions={0}
  %all-reduce.1 = bf16[16,8]{1,0} all-reduce(%y), replica_groups=[2,4]<=[8]T(1,0)
  %rs = f32[8]{0} reduce-scatter(%z), replica_groups={{0,1,2,3}}
  %cp = f32[128]{0} collective-permute(%w), source_target_pairs={{0,1}}
"""
    out = H.collective_bytes(text)
    assert out["counts"] == {"all-gather": 1, "all-reduce": 1,
                             "reduce-scatter": 1, "collective-permute": 1}
    ag = 64 * 32 * 4 * (2 - 1) / 2
    ar = 2 * 16 * 8 * 2 * (4 - 1) / 4
    rs = 8 * 4 * (4 - 1)
    cp = 128 * 4
    assert abs(out["total_bytes"] - (ag + ar + rs + cp)) < 1e-6


def test_mesh_constructors():
    from repro.launch.mesh import make_host_mesh
    m = make_host_mesh()
    assert set(m.axis_names) == {"data", "model"}


def test_dryrun_records_exist_and_wellformed():
    """If the full sweep has produced artifacts, validate their schema."""
    import pathlib
    d = pathlib.Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    files = list(d.glob("*.json")) if d.exists() else []
    if not files:
        pytest.skip("dry-run artifacts not generated yet")
    for f in files:
        rec = json.loads(f.read_text())
        assert "arch" in rec and "mesh" in rec
        if not rec.get("skipped"):
            assert rec["full"]["flops"] >= 0
            assert "memory" in rec["full"]
