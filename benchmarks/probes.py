"""Verification probes behind the EXPERIMENTS.md §Dry-run methodology.

Run: PYTHONPATH=src python -m benchmarks.probes
(spawns subprocesses: each probe needs its own forced device count).

Probe 1 — cost_analysis reports per-device flops for SPMD modules.
Probe 2 — scan/while bodies are counted exactly once.
Probe 3 — XLA keeps f32 accumulators through TP all-reduces (why the
          bf16_reduce experiment existed; §Perf it3).
"""
from __future__ import annotations

import subprocess
import sys

PROBE1 = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro import compat
from jax.sharding import PartitionSpec as P, NamedSharding
mesh = compat.make_mesh((4,), ("d",))
M = 1024
sh = lambda s: NamedSharding(mesh, s)
c = jax.jit(lambda x, w: x @ w).lower(
    jax.ShapeDtypeStruct((M, M), jnp.float32, sharding=sh(P("d", None))),
    jax.ShapeDtypeStruct((M, M), jnp.float32, sharding=sh(P(None, None)))
).compile()
got = c.cost_analysis()["flops"]
assert abs(got - 2 * M**3 / 4) / (2 * M**3 / 4) < 0.01, got
print(f"probe1 OK: sharded matmul flops {got:.3g} == global/4")
"""

PROBE2 = """
import jax, jax.numpy as jnp
from repro import compat
M = 1024
def g(x):
    def body(c, _):
        return c @ x, None
    y, _ = jax.lax.scan(body, jnp.eye(M, dtype=jnp.float32), None, length=7)
    return y
c = jax.jit(g).lower(jax.ShapeDtypeStruct((M, M), jnp.float32)).compile()
got = c.cost_analysis()["flops"]
assert got < 1.5 * 2 * M**3, got  # 7x body would be ~1.5e10
print(f"probe2 OK: scan-of-7 flops {got:.3g} ~= one body (trip count ignored)")
"""

PROBE3 = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp
from repro import compat
from jax.sharding import PartitionSpec as P, NamedSharding
mesh = compat.make_mesh((16,), ("model",))
sds = lambda s, spec: jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                           sharding=NamedSharding(mesh, spec))
c = jax.jit(lambda x, w: x @ w).lower(
    sds((8, 1024), P(None, "model")), sds((1024, 512), P("model", None))
).compile()
txt = c.as_text()
assert any("f32" in l and "all-reduce" in l for l in txt.splitlines()
           if "-done" not in l)
print("probe3 OK: bf16 matmul with sharded contraction all-reduces in f32")
"""


def main():
    for i, probe in enumerate((PROBE1, PROBE2, PROBE3), 1):
        r = subprocess.run([sys.executable, "-c", probe],
                           capture_output=True, text=True, timeout=600)
        if r.returncode:
            print(f"probe{i} FAILED:\n{r.stderr[-1500:]}")
            sys.exit(1)
        print(r.stdout.strip())


if __name__ == "__main__":
    main()
