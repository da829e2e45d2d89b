"""v5e compile rehearsal: the max-flow kernels and cycle loop compiled for
a described (not attached) TPU v5e chip.

Nothing runs — these prove Mosaic and XLA accept the programs at the
real single-instance size (``washington_rlg(4096, 256)``: 1,048,578
vertices, 6,281,676 arcs) and at a serving bucket, with a Mosaic kernel
(``tpu_custom_call``) in every kernel program and every program inside
the chip's 16 GB.  The topology is described inside a module fixture, so
only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import batched
from repro.core import pushrelabel as pr
from repro.kernels.revsearch import bcsr_rev_search
from repro.kernels.segmin import tile_min_neighbor

N_REAL, A_REAL = 1_048_578, 6_281_676
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # programs compiled for a described chip can be written to the
    # persistent cache but never read back here: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _check(compiled, kernel=True):
    if kernel:
        assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


@pytest.mark.parametrize("form", ["avq", "dense"])
def test_segmin_compiles_at_real_size(one_chip, form):
    avq = _spec(one_chip, (N_REAL,)) if form == "avq" else None
    compiled = tile_min_neighbor.lower(
        avq, _spec(one_chip, (N_REAL + 1,)), _spec(one_chip, (A_REAL,)),
        n=N_REAL, interpret=False).compile()
    _check(compiled)


def test_revsearch_compiles_at_real_size(one_chip):
    compiled = bcsr_rev_search.lower(
        _spec(one_chip, (N_REAL,)), _spec(one_chip, (N_REAL + 1,)),
        _spec(one_chip, (A_REAL,)), _spec(one_chip, (A_REAL,)),
        interpret=False).compile()
    _check(compiled)


@pytest.mark.parametrize("mode", ["vc", "vc_kernel_bsearch"])
def test_batched_cycle_loop_compiles_at_serving_bucket(one_chip, mode):
    """A small serving bucket (B=8, n=128, A=512): queues shorter than
    one tile of entries, padded up to it, and a grid over the batch."""
    b, n, a = 8, 128, 512
    bg = batched.BatchedDeviceGraph(
        indptr=_spec(one_chip, (b, n + 1)), heads=_spec(one_chip, (b, a)),
        tails=_spec(one_chip, (b, a)), rev=_spec(one_chip, (b, a)),
        n=_spec(one_chip, (b,)), num_arcs=_spec(one_chip, (b,)),
        s=_spec(one_chip, (b,)), t=_spec(one_chip, (b,)))
    state = batched.BatchedPRState(res=_spec(one_chip, (b, a)),
                                   h=_spec(one_chip, (b, n)),
                                   e=_spec(one_chip, (b, n)))
    meta = pr.GraphMeta(n=n, num_arcs=a, deg_max=16, layout="batched-bcsr")
    compiled = batched.batched_run_cycles.lower(
        bg, meta, state, mode=mode, max_cycles=128,
        interpret=False).compile()
    _check(compiled, kernel=mode != "vc")


def test_bucketed_cycle_loop_compiles(one_chip):
    """The unbatched ``vc`` loop, its step switched over the frontier
    ladder's rungs (a ``conditional`` of several branches)."""
    n, a = 2_050, 11_776
    assert len(pr.frontier_ladder(n, a)) > 4
    g = pr.DeviceGraph(indptr=_spec(one_chip, (n + 1,)),
                       heads=_spec(one_chip, (a,)),
                       tails=_spec(one_chip, (a,)),
                       rev=_spec(one_chip, (a,)))
    state = pr.PRState(res=_spec(one_chip, (a,)), h=_spec(one_chip, (n,)),
                       e=_spec(one_chip, (n,)))
    meta = pr.GraphMeta(n=n, num_arcs=a, deg_max=32, layout="bcsr")
    compiled = pr.run_cycles.lower(g, meta, state, n - 2, n - 1, mode="vc",
                                   max_cycles=1024,
                                   budget=_spec(one_chip, ())).compile()
    assert " conditional(" in compiled.as_text()
    _check(compiled, kernel=False)


def test_phase2_compiles_at_real_size(one_chip):
    """Phase 2 at the real single-instance size: the dense drain's
    cumsum, gathers and sorted segment sum over every arc, under the
    height sweeps' loops."""
    from repro.core import phase2

    g = pr.DeviceGraph(indptr=_spec(one_chip, (N_REAL + 1,)),
                       heads=_spec(one_chip, (A_REAL,)),
                       tails=_spec(one_chip, (A_REAL,)),
                       rev=_spec(one_chip, (A_REAL,)))
    meta = pr.GraphMeta(n=N_REAL, num_arcs=A_REAL, deg_max=32,
                        layout="bcsr")
    compiled = phase2.phase2_run.lower(
        g, meta, _spec(one_chip, (A_REAL,)), _spec(one_chip, (A_REAL,)),
        _spec(one_chip, (N_REAL,)), _spec(one_chip, ()),
        _spec(one_chip, ())).compile()
    _check(compiled, kernel=False)
