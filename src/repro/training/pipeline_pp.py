"""GPipe pipeline parallelism over the ``pod`` axis.

The stacked-block parameter dim is sharded across ``pod`` (stage s holds
blocks [s*nb/S, (s+1)*nb/S)); activations flow stage-to-stage through
``collective_permute`` on a tick schedule: at tick t, stage s works on
microbatch ``t - s`` (the classic GPipe wavefront, M + S - 1 ticks).
Embedding runs on stage 0, the LM head + loss on the last stage; the loss
is psum'd so every stage returns the same scalar.

The whole schedule is differentiable (collective_permute transposes to the
reverse permute), so ``jax.grad`` of this loss is pipeline-parallel
training.  Numerical equivalence with the single-program model is asserted
in tests/test_pipeline_pp.py.

This is the explicit hand-scheduled path; it composes with the
cross-pod gradient compression in ``grad_compress.hierarchical_pod_psum``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import transformer as T


def make_pp_loss(cfg, mesh, *, stages: int, microbatches: int):
    """Returns loss_fn(params, batch) running the GPipe schedule.

    Constraints: cfg.n_blocks % stages == 0, batch % microbatches == 0,
    len(cfg.block_pattern) arbitrary. ``pod`` must be a mesh axis of size
    ``stages``.
    """
    nb = cfg.n_blocks
    assert nb % stages == 0
    per_stage = nb // stages
    npat = len(cfg.block_pattern)

    def local_loss(params, tokens, labels):
        # params["blocks"] leaves arrive as (1, per_stage, ...): local blocks
        blocks = jax.tree.map(lambda a: a[0], params["blocks"])
        embed = params["embed"]
        fnorm = params["final_norm"]
        stage = jax.lax.axis_index("pod")
        m, bm, s = tokens.shape[0], tokens.shape[1], tokens.shape[2]
        d = cfg.d_model
        positions = jnp.broadcast_to(jnp.arange(s), (bm, s))

        def run_my_blocks(x):
            def body(x, bp):
                for i, kind in enumerate(cfg.block_pattern):
                    x, _, _ = T.apply_layer(cfg, kind, bp[f"p{i}"], x,
                                            positions=positions,
                                            mode="train")
                return x, None
            x, _ = jax.lax.scan(body, x, blocks)
            return x

        ticks = microbatches + stages - 1
        x0 = jnp.zeros((bm, s, d), cfg.dtype)

        def tick_fn(carry, t):
            x_slot, loss_acc = carry
            # receive previous stage's output (ring; stage0's input unused)
            perm = [(i, (i + 1) % stages) for i in range(stages)]
            x_in = jax.lax.ppermute(x_slot, "pod", perm)
            mb = t - stage  # microbatch this stage handles at tick t
            active = (mb >= 0) & (mb < microbatches)
            mb_c = jnp.clip(mb, 0, microbatches - 1)
            tok = jax.lax.dynamic_index_in_dim(tokens, mb_c, 0, False)
            x_first = embed[tok].astype(cfg.dtype)
            x = jnp.where(stage == 0, x_first, x_in)
            y = run_my_blocks(x)
            y = jnp.where(active[..., None, None, None].squeeze(), y,
                          jnp.zeros_like(y))
            # last stage: head + loss for its active microbatch
            lab = jax.lax.dynamic_index_in_dim(labels, mb_c, 0, False)
            xl = L.rms_norm(y, fnorm)
            logits = jnp.einsum("bsd,vd->bsv", xl,
                                embed).astype(jnp.float32)
            from repro.training.train_step import cross_entropy
            ce = cross_entropy(logits, lab, cfg.vocab)
            is_last = stage == stages - 1
            loss_acc = loss_acc + jnp.where(active & is_last, ce, 0.0)
            return (y, loss_acc), None

        # the loss rides through the schedule as a (1,) array and leaves
        # the shard_map tiled over `pod`: legacy shard_map (jax <= 0.4.37)
        # raises _SpecError on any unmapped float32[] crossing its
        # boundary (both the scalar output and the scalar scan-carry
        # residual of the backward pass) — the caller takes [0]
        (x_slot, loss_acc), _ = jax.lax.scan(
            tick_fn, (x0, jnp.zeros((1,), jnp.float32)), jnp.arange(ticks))
        total = jax.lax.psum(loss_acc, "pod") / microbatches
        return total

    blocks_spec = jax.tree.map(lambda _: P("pod"), T.param_specs(cfg)["blocks"],
                               is_leaf=lambda x: isinstance(x, L.PSpec))
    in_specs = ({"embed": P(), "final_norm": P(), "blocks": blocks_spec},
                P(), P())
    pp = jax.shard_map(local_loss, mesh=mesh, in_specs=in_specs,
                       out_specs=P("pod"), check_vma=False)

    def loss_fn(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        b = tokens.shape[0]
        assert b % microbatches == 0
        tok = tokens.reshape(microbatches, b // microbatches, -1)
        lab = labels.reshape(microbatches, b // microbatches, -1)
        # reshape stacked blocks (nb, ...) -> (stages, per_stage, ...)
        p = dict(params)
        p["blocks"] = jax.tree.map(
            lambda a: a.reshape((stages, per_stage) + a.shape[1:]),
            params["blocks"])
        return pp(p, tok, lab)[0]  # all stages carry the same psum'd loss

    return loss_fn
