"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant loop (checkpoint/restart, straggler accounting) on
whatever devices exist; ``--smoke`` selects the reduced config so the full
path runs on CPU.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args(argv)

    from repro.configs import registry
    from repro.data.pipeline import TokenPipeline
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T
    from repro.runtime.fault import run_loop
    from repro.training import optimizer as O
    from repro.training.train_step import make_train_step

    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    mesh = make_host_mesh()
    opt = O.make_optimizer(cfg.optimizer, lr=args.lr)
    compressor = None
    comp_state = [None]
    if args.compress_grads:
        from repro.training.grad_compress import \
            make_error_feedback_compressor
        cinit, compressor = make_error_feedback_compressor()
    raw_step = make_train_step(cfg, opt, compressor=compressor,
                               microbatches=args.microbatches)
    jit_step = jax.jit(raw_step)

    def make_state():
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        if args.compress_grads:
            comp_state[0] = cinit(params)
        return params, opt.init(params)

    def step_fn(params, opt_state, batch):
        batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
        if args.compress_grads:
            p, o, comp_state[0], m = jit_step(params, opt_state, batch,
                                              comp_state[0])
            return p, o, m
        return jit_step(params, opt_state, batch)

    pipe = TokenPipeline(cfg.vocab, args.batch, args.seq, seed=0,
                         ext_embed_len=(cfg.enc_len if cfg.is_encoder_decoder
                                        else cfg.img_tokens),
                         d_model=cfg.d_model)
    with jax.set_mesh(mesh):
        report = run_loop(ckpt_dir=args.ckpt_dir, total_steps=args.steps,
                          make_state=make_state, step_fn=step_fn,
                          pipeline=pipe, ckpt_every=args.ckpt_every)
    n = cfg.param_count()
    print(f"arch={cfg.name} params~{n/1e6:.1f}M steps={report.steps_done} "
          f"loss={report.last_loss:.4f} restarts={report.restarts} "
          f"stragglers={report.straggler_steps} "
          f"median_step={np.median(report.step_times)*1e3:.1f}ms")


if __name__ == "__main__":
    main()
