"""Per-mode eqn-count baselines: the scan-compiled-vs-unrolled probe.

One steady-state cycle step traced through one scan-compiled engine
chunk vs the same chunk Python-unrolled (``engine.scan_chunk_eqns``) —
the traced-program-size saving the sweep engine exists for.  The counts
are a property of the *step trace*, not of the graph (every graph of a
layout lowers the same step body), so they are probed once on a tiny
canonical fixture and recorded as the repo's per-mode baselines:

* ``repro.launch.analyze`` embeds them in ``ANALYSIS.json`` under
  ``"baselines"``;
* ``benchmarks/kernel_cycles.py`` consumes them (from a live
  ``ANALYSIS.json`` when present, else computed fresh) instead of
  re-deriving the probe per benchmark graph, as it historically did.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path

__all__ = ["scan_chunk_baselines", "load_baselines", "mode_baselines"]

#: the canonical probe fixture (any graph yields identical counts; this
#: one is tiny so the abstract trace is instant)
_PROBE_GRAPH = (60, 240, 7)  # (n, m, seed)


@functools.lru_cache(maxsize=None)
def scan_chunk_baselines(modes: tuple[str, ...] | None = None,
                         chunk: int | None = None) -> dict:
    """mode -> ``{"scan_chunk", "scanned_eqns", "unrolled_eqns"}``,
    probed fresh via ``engine.scan_chunk_eqns``."""
    import jax.numpy as jnp

    from repro.core import engine, globalrelabel
    from repro.core import pushrelabel as pr
    from repro.core.csr import build_residual
    from repro.graphs import generators as G

    if modes is None:
        modes = pr.ALL_MODES
    chunk = engine.DEFAULT_CHUNK if chunk is None else int(chunk)

    n, m, seed = _PROBE_GRAPH
    adj, s, t = G.random_sparse(n, m, seed=seed)
    r = build_residual(adj, "bcsr")
    g, meta, res0 = pr.to_device(r)
    state0 = pr.preflow(g, meta, res0, s)
    state0, _, _ = globalrelabel.global_relabel(g, meta, state0, s, t)

    out = {}
    for mode in modes:
        step = pr._make_step(mode)
        scanned, unrolled = engine.scan_chunk_eqns(
            lambda c, _step=step: (_step(g, meta, c[0], s, t), c[1] + 1),
            lambda c: c[1] < jnp.int32(8),
            (state0, jnp.int32(0)), chunk)
        out[mode] = {"scan_chunk": chunk, "scanned_eqns": scanned,
                     "unrolled_eqns": unrolled}
    return out


def load_baselines(path: str | Path) -> dict | None:
    """The ``"baselines"`` section of an ``ANALYSIS.json``, or None if
    the file is absent/unreadable/missing the section."""
    path = Path(path)
    if not path.exists():
        return None
    try:
        payload = json.loads(path.read_text())
    except ValueError:
        return None
    base = payload.get("baselines")
    return base if isinstance(base, dict) and base else None


def mode_baselines(path: str | Path | None = None) -> dict:
    """The per-mode baselines: from ``path`` (an ``ANALYSIS.json``)
    when given and readable, else probed fresh."""
    if path is not None:
        loaded = load_baselines(path)
        if loaded is not None:
            return loaded
    return scan_chunk_baselines()
