"""Serving launcher: batched prefill + decode with the KV/state cache.

``python -m repro.launch.serve --arch rwkv6-1.6b --smoke --tokens 32``
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-1.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args(argv)

    from repro.configs import registry
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T
    from repro.training.train_step import (make_decode_step,
                                           make_prefill_step)

    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    mesh = make_host_mesh()
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params = T.init_params(cfg, key)
        toks = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                  cfg.vocab)
        ext = None
        if cfg.is_encoder_decoder:
            ext = jax.random.normal(key, (args.batch, cfg.enc_len,
                                          cfg.d_model), cfg.dtype)
        elif cfg.img_tokens:
            ext = jax.random.normal(key, (args.batch, cfg.img_tokens,
                                          cfg.d_model), cfg.dtype)
        prefill = jax.jit(make_prefill_step(
            cfg, max_len=args.prompt_len + args.tokens + 1))
        decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
        t0 = time.time()
        last, cache = prefill(params, toks, ext) if ext is not None \
            else prefill(params, toks)
        nxt = jnp.argmax(last, -1)[:, None].astype(jnp.int32)
        out = [nxt]
        t1 = time.time()
        for _ in range(args.tokens - 1):
            nxt, _, cache = decode(params, cache, nxt)
            nxt = nxt[:, None]
            out.append(nxt)
        jax.block_until_ready(out[-1])
        t2 = time.time()
    seqs = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={args.batch} prefill={t1-t0:.3f}s "
          f"decode={args.tokens - 1} tok in {t2-t1:.3f}s "
          f"({(args.tokens-1)*args.batch/max(t2-t1,1e-9):.1f} tok/s)")
    print("sampled ids:", seqs[0, :12].tolist())


if __name__ == "__main__":
    main()
