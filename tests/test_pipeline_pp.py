"""GPipe pipeline parallelism: numerical equivalence with the single-program
model (loss and gradients), in a 4-device subprocess."""
import pathlib
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys, dataclasses
sys.path.insert(0, %(src)r)
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.registry import get_smoke_config
from repro.models import transformer as T
from repro.training.train_step import make_loss_fn
from repro.training.pipeline_pp import make_pp_loss

cfg = dataclasses.replace(get_smoke_config("qwen3-4b"), n_layers=4,
                          dtype=jnp.float32, remat=False)
mesh = compat.make_mesh((2,), ("pod",))
key = jax.random.PRNGKey(0)
params = T.init_params(cfg, key)
batch = {
    "tokens": jax.random.randint(key, (4, 16), 0, cfg.vocab),
    "labels": jax.random.randint(key, (4, 16), 0, cfg.vocab),
}
ref_loss_fn = make_loss_fn(cfg)
ref_loss, _ = ref_loss_fn(params, batch)
pp_loss_fn = make_pp_loss(cfg, mesh, stages=2, microbatches=2)
with jax.set_mesh(mesh):
    pp_loss = jax.jit(pp_loss_fn)(params, batch)
    np.testing.assert_allclose(float(pp_loss), float(ref_loss),
                               rtol=1e-4, atol=1e-4)
    g_ref = jax.grad(lambda p: ref_loss_fn(p, batch)[0])(params)
    g_pp = jax.jit(jax.grad(lambda p: pp_loss_fn(p, batch)))(params)
    flat_r, _ = jax.tree.flatten(g_ref)
    flat_p, _ = jax.tree.flatten(g_pp)
    for a, b in zip(flat_r, flat_p):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-3, atol=5e-3)
print("PP-OK")
"""


@pytest.mark.slow
def test_gpipe_matches_reference():
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT % {"src": src}],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PP-OK" in r.stdout
