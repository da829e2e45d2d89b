import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we compile two things:

1. the FULL production module (scan-over-layers, flash attention) — this is
   the compile/sharding proof and the source of ``memory_analysis()``;
2. two small *unrolled* variants (1 and 2 superblocks, inner scans replaced
   by flop-equivalent unscanned forms) whose ``cost_analysis()`` and HLO
   collective bytes extrapolate linearly to the full depth:

       C_total = C_1 + (n_blocks - 1) * (C_2 - C_1)

   (XLA's cost analysis counts while-loop bodies exactly once and reports
   per-device numbers — measured in EXPERIMENTS.md §Dry-run.)

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import dataclasses
import json
import pathlib
import subprocess
import sys
import time

import jax
import numpy as np

REPO = pathlib.Path(__file__).resolve().parents[3]
OUT_DIR = REPO / "experiments" / "dryrun"


def _mesh_tag(multi_pod):
    return "2x16x16" if multi_pod else "16x16"


def _cost_variant_cfg(cfg, n_super, seq, k_chunks):
    """Unrolled (no layer scan) variant with the *deployed* flash/chunked
    dataflow, all inner scans set to exactly ``k_chunks`` trip counts."""
    npat = len(cfg.block_pattern)
    chunk = max(1, seq // k_chunks)
    kw = dict(n_layers=npat * n_super, scan_layers=False,
              attn_chunk=chunk, ssm_chunk=chunk)
    if cfg.is_encoder_decoder:
        kw["n_enc_layers"] = n_super
    return dataclasses.replace(cfg, **kw)


def _lower_lm(cfg, cell, mesh):
    from repro.launch import specs as S
    from repro.training import optimizer as O
    from repro.training.train_step import (make_decode_step,
                                           make_prefill_step,
                                           make_train_step)
    args, kind = S.input_specs(cfg, cell, mesh)
    if kind == "train":
        opt = O.make_optimizer(cfg.optimizer)
        gs = None
        if getattr(cfg, "pin_grads", False):
            from repro.models import transformer as T
            gs = T.param_shardings(cfg, mesh)
        fn = make_train_step(cfg, opt, grad_shardings=gs)
        donate = (0, 1)
    elif kind == "prefill":
        fn = make_prefill_step(cfg)
        donate = ()
    else:
        fn = make_decode_step(cfg)
        donate = (1,)
    with jax.set_mesh(mesh):
        return jax.jit(fn, donate_argnums=donate).lower(*args)


def _graph_specs(cell, mesh, axes, mode):
    """Synthetic regular-graph ShapeDtypeStructs for the WBPR superstep."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import distributed as D
    nshards = int(np.prod([mesh.shape[a] for a in axes]))
    v, a = cell.batch, cell.seq
    vs, amax = v // nshards, a // nshards
    meta = D.DistMeta(n=v, num_arcs=a, vs=vs, amax=amax, nshards=nshards,
                      s=0, t=v - 1, mode=mode)
    sh = lambda spec: NamedSharding(mesh, spec)
    sds = jax.ShapeDtypeStruct
    g = D.DistGraph(
        indptr=sds((nshards, vs + 1), jnp.int32, sharding=sh(P(axes))),
        heads=sds((nshards, amax), jnp.int32, sharding=sh(P(axes))),
        rev=sds((nshards, amax), jnp.int32, sharding=sh(P(axes))),
        tail_local=sds((nshards, amax), jnp.int32, sharding=sh(P(axes))),
    )
    if mode in ("sharded", "sparse"):
        res = sds((nshards, amax), jnp.int32, sharding=sh(P(axes)))
    else:
        res = sds((a,), jnp.int32, sharding=sh(P()))
    h = sds((v,), jnp.int32, sharding=sh(P()))
    e = sds((v,), jnp.int32, sharding=sh(P()))
    return meta, g, res, h, e


def _lower_graph(cell, mesh, mode, cycles=64):
    from repro.core import distributed as D
    axes = tuple(mesh.axis_names)
    meta, g, res, h, e = _graph_specs(cell, mesh, axes, mode)
    superstep = D.make_superstep(meta, axes, cycles=cycles, mesh=mesh)
    with jax.set_mesh(mesh):
        full = jax.jit(superstep, donate_argnums=(1, 2, 3)).lower(g, res, h, e)
        step = D.make_dist_step(meta, axes, mesh)
        step_l = jax.jit(step).lower(g.indptr, g.heads, g.rev, res, h, e)
        sweep = D.make_gr_sweep(meta, axes, mesh)
        sweep_l = jax.jit(sweep).lower(g.indptr, g.heads, g.rev,
                                       g.tail_local, res, h)
    return full, step_l, sweep_l, meta


def _analytic_lm(cfg, cell):
    n_active = cfg.active_param_count()
    n_total = cfg.param_count()
    if cell.kind == "train":
        tokens = cell.batch * cell.seq
        model_flops = 6 * n_active * tokens
    elif cell.kind == "prefill":
        tokens = cell.batch * cell.seq
        model_flops = 2 * n_active * tokens
    else:
        tokens = cell.batch
        model_flops = 2 * n_active * tokens
    return {"params": n_total, "active_params": n_active,
            "tokens": tokens, "model_flops": model_flops}


def _apply_overrides(cfg, opt: str):
    import dataclasses as dc
    if not opt:
        return cfg, ""
    kw = {}
    for item in opt.split(","):
        k, _, v = item.partition("=")
        kw[k.strip()] = bool(int(v)) if v in ("0", "1") else v
    slug = "-".join(k for k, v in kw.items() if v)
    return dc.replace(cfg, **kw), slug


def run_cell(arch: str, shape: str, multi_pod: bool,
             graph_mode: str = "replicated", opt: str = "") -> dict:
    from repro.configs import registry
    from repro.launch import hlo_analysis as H
    from repro.launch import shapes as SH
    from repro.launch.mesh import make_production_mesh

    cfg = registry.get_config(arch)
    opt_slug = ""
    if getattr(cfg, "family", None) != "graph":
        cfg, opt_slug = _apply_overrides(cfg, opt)
    cells = {c.name: c for c in SH.cells_for(cfg)}
    if shape not in cells:
        return {"arch": arch, "shape": shape, "mesh": _mesh_tag(multi_pod),
                "skipped": True,
                "reason": "full-attention arch: long-context decode is "
                          "quadratic; skipped per DESIGN.md §5"}
    cell = cells[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    ndev = int(np.prod(list(mesh.shape.values())))
    rec = {"arch": arch, "shape": shape, "mesh": _mesh_tag(multi_pod),
           "devices": ndev, "kind": cell.kind, "skipped": False,
           "opt": opt or None}
    if opt_slug:
        rec["opt_slug"] = opt_slug
    t0 = time.time()

    if getattr(cfg, "family", None) == "graph":
        full, step_l, sweep_l, meta = _lower_graph(cell, mesh, graph_mode)
        rec["graph_mode"] = graph_mode
        rec["lower_s"] = time.time() - t0
        t1 = time.time()
        compiled = full.compile()
        rec["compile_s"] = time.time() - t1
        rec["full"] = H.cost_summary(compiled)
        cycles = 64
        step_c = H.cost_summary(step_l.compile())
        sweep_c = H.cost_summary(sweep_l.compile())
        est_sweeps = 24  # ~diameter of the synthetic graphs (documented)
        rec["per_iter"] = {"step": step_c, "gr_sweep": sweep_c}
        rec["extrapolated"] = {
            "flops": cycles * step_c["flops"] + est_sweeps * sweep_c["flops"],
            "bytes_accessed": cycles * step_c["bytes_accessed"]
            + est_sweeps * sweep_c["bytes_accessed"],
            "collective_bytes":
                cycles * step_c["collectives"]["total_bytes"]
                + est_sweeps * sweep_c["collectives"]["total_bytes"],
        }
        rec["analytic"] = {"vertices": cell.batch, "arcs": cell.seq,
                           "cycles": cycles, "est_sweeps": est_sweeps}
        return rec

    # LM cell: full module (compile + memory proof)
    full = _lower_lm(cfg, cell, mesh)
    rec["lower_s"] = time.time() - t0
    t1 = time.time()
    compiled = full.compile()
    rec["compile_s"] = time.time() - t1
    rec["full"] = H.cost_summary(compiled)

    if multi_pod:
        # multi-pod pass proves the "pod" axis shards + fits; the roofline
        # table (cost extrapolation) is single-pod only (spec §Roofline)
        rec["analytic"] = _analytic_lm(cfg, cell)
        return rec

    # Cost extrapolation from three unrolled variants with the deployed
    # flash/chunked dataflow.  XLA counts every scan body once, so with
    #   A = (1 superblock, K=4 chunks), B = (1 sb, K=8), C = (2 sb, K=4):
    #   body_sb      = 2 (A - B)         (per-chunk work is linear in chunk)
    #   total = 2A - C + nb (C - A) + nb (K-1) body_sb
    # Degenerates to A + (nb-1)(C-A) when nothing is chunk-scanned (decode).
    nb = cfg.n_blocks
    k_dep = 4
    variants = [(1, 4), (1, 8), (2, 4)]
    costs = []
    for nsb, k in variants:
        cfg_v = _cost_variant_cfg(cfg, nsb, cell.seq, k)
        lv = _lower_lm(cfg_v, cell, mesh)
        costs.append(H.cost_summary(lv.compile()))
    ca, cb, cc = costs

    def _coll(c):
        return c["collectives"]["total_bytes"]

    extr = {}
    for key, get in [("flops", lambda c: c["flops"]),
                     ("bytes_accessed", lambda c: c["bytes_accessed"]),
                     ("transcendentals", lambda c: c["transcendentals"]),
                     ("collective_bytes", _coll)]:
        a, b, c = get(ca), get(cb), get(cc)
        body = max(0.0, 2.0 * (a - b))
        extr[key] = (2 * a - c) + nb * (c - a) + nb * (k_dep - 1) * body
    extr["collectives_by_op_1sb"] = ca["collectives"]["by_op"]
    rec["variant_costs"] = {"c1": ca, "c1_halfchunk": cb, "c2": cc}
    rec["extrapolated"] = extr
    rec["analytic"] = _analytic_lm(cfg, cell)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--graph-mode", default="replicated")
    ap.add_argument("--opt", default="",
                    help="perf-knob overrides, e.g. shard_activations=1")
    ap.add_argument("--all", action="store_true",
                    help="run every cell in subprocesses")
    ap.add_argument("--out-dir", default=str(OUT_DIR))
    args = ap.parse_args(argv)
    out_dir = pathlib.Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        from repro.configs import registry
        from repro.launch import shapes as SH
        jobs = []
        for arch in registry.ARCH_IDS:
            cfg = registry.get_config(arch)
            names = [c.name for c in SH.cells_for(cfg)]
            if getattr(cfg, "family", None) != "graph":
                names = list(SH.LM_SHAPES)  # include skips for the record
            for shape in names:
                for mp in ((False, True) if args.both_meshes else
                           (args.multi_pod,)):
                    jobs.append((arch, shape, mp))
        failures = []
        for arch, shape, mp in jobs:
            tag = f"{arch}__{shape}__{_mesh_tag(mp)}"
            fout = out_dir / f"{tag}.json"
            if fout.exists():
                print(f"[skip-cached] {tag}", flush=True)
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape,
                   "--graph-mode", args.graph_mode,
                   "--out-dir", str(out_dir)]
            if mp:
                cmd.append("--multi-pod")
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True,
                               env={**os.environ, "PYTHONPATH":
                                    str(REPO / "src")})
            ok = r.returncode == 0 and fout.exists()
            print(f"[{'ok' if ok else 'FAIL'}] {tag} ({time.time()-t0:.0f}s)",
                  flush=True)
            if not ok:
                failures.append(tag)
                (out_dir / f"{tag}.err").write_text(
                    r.stdout[-4000:] + "\n---\n" + r.stderr[-8000:])
        print(f"done: {len(jobs) - len(failures)}/{len(jobs)} ok")
        if failures:
            print("failures:", failures)
            sys.exit(1)
        return

    assert args.arch and args.shape
    rec = run_cell(args.arch, args.shape, args.multi_pod, args.graph_mode,
                   args.opt)
    tag = f"{args.arch}__{args.shape}__{rec['mesh']}"
    if rec.get("opt_slug"):
        tag += f"__opt-{rec['opt_slug']}"
    if rec.get("graph_mode") and rec["graph_mode"] != "replicated":
        tag += f"__{rec['graph_mode']}"
    fout = out_dir / f"{tag}.json"
    fout.write_text(json.dumps(rec, indent=2, default=float))
    mem = rec.get("full", {}).get("memory", {})
    print(json.dumps({k: rec.get(k) for k in
                      ("arch", "shape", "mesh", "skipped", "compile_s")},
                     default=float))
    if not rec.get("skipped"):
        print("memory_analysis:", mem)
        print("cost_analysis(full):",
              {k: rec["full"].get(k) for k in ("flops", "bytes_accessed")})
        print("extrapolated:", rec.get("extrapolated"))


if __name__ == "__main__":
    main()
