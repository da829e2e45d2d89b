"""Model assembly: pattern-based layer stacks, scanned over blocks.

Parameters live in a pytree:

    {"embed": (V, D), "final_norm": (D,),
     "blocks": {"p0": {...}, "p1": {...}},      # leaves stacked (n_blocks, ...)
     "encoder": {...}}                          # enc-dec only

``forward`` covers three modes:
  * train:   full-sequence causal, returns logits (+ MoE aux loss)
  * prefill: full-sequence, also returns a filled KV/state cache
  * decode:  one token against the cache (``serve_step``)

Every weight leaf carries logical sharding axes (see ``layers.PSpec`` and
``sharding.rules``); ``param_specs``/``shape_tree`` produce either real
initialised arrays or ShapeDtypeStructs with NamedShardings (dry-run).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import rwkv as R
from repro.sharding import rules as SR


# ---------------------------------------------------------------------------
# parameter spec tree
# ---------------------------------------------------------------------------

def _mixer_ffn(kind: str):
    mixer, _, ffn = kind.partition("+")
    return mixer, (ffn or None)


def layer_specs(cfg, kind: str) -> dict:
    mixer, ffn = _mixer_ffn(kind)
    s: dict = {}
    if mixer == "attn":
        s["attn"] = L.attn_specs(cfg)
    elif mixer == "cross":
        s["cross"] = L.attn_specs(cfg, cross=True)
    elif mixer == "attn_cross":
        s["attn"] = L.attn_specs(cfg)
        s["cross"] = L.attn_specs(cfg, cross=True)
        s["cross"]["norm2"] = L.PSpec((cfg.d_model,), (None,), "ones")
    elif mixer == "mamba":
        s["mamba"] = M.mamba_specs(cfg)
    elif mixer == "rwkv":
        s["rwkv"] = R.rwkv_specs(cfg)
    else:
        raise ValueError(kind)
    if ffn == "mlp":
        s["mlp"] = L.mlp_specs(cfg)
    elif ffn == "moe":
        s["moe"] = MOE.moe_specs(cfg)
    return s


def param_specs(cfg) -> dict:
    d = cfg.d_model
    specs: dict = {
        "embed": L.PSpec((cfg.vocab, d), ("vocab", "fsdp")),
        "final_norm": L.PSpec((d,), (None,), "ones"),
        "blocks": {},
    }
    for i, kind in enumerate(cfg.block_pattern):
        sub = layer_specs(cfg, kind)
        # leaves always stacked (n_blocks, ...): identical tree for the
        # scanned and unrolled execution paths
        sub = jax.tree.map(
            lambda ps: L.PSpec((cfg.n_blocks,) + ps.shape,
                               (None,) + ps.logical, ps.init, ps.scale),
            sub, is_leaf=lambda x: isinstance(x, L.PSpec))
        specs["blocks"][f"p{i}"] = sub
    if cfg.is_encoder_decoder:
        enc = layer_specs(cfg, "attn+mlp")
        enc = jax.tree.map(
            lambda ps: L.PSpec((cfg.n_enc_layers,) + ps.shape,
                               (None,) + ps.logical, ps.init, ps.scale),
            enc, is_leaf=lambda x: isinstance(x, L.PSpec))
        specs["encoder"] = {"blocks": enc,
                            "norm": L.PSpec((d,), (None,), "ones")}
    return specs


def init_params(cfg, key) -> dict:
    specs = param_specs(cfg)
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, L.PSpec))
    keys = jax.random.split(key, len(leaves))
    params = [L.init_param(k, ps, cfg.dtype) for k, ps in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, params)


def shape_tree(cfg, mesh, rules=None) -> dict:
    """ShapeDtypeStructs with NamedShardings — dry-run inputs, no allocation."""
    rules = {**(rules or {}), **SR.rules_for_config(cfg)}
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda ps: jax.ShapeDtypeStruct(
            ps.shape, cfg.dtype,
            sharding=SR.sharding_for(mesh, ps.logical, ps.shape, rules)),
        specs, is_leaf=lambda x: isinstance(x, L.PSpec))


def param_shardings(cfg, mesh, rules=None) -> dict:
    rules = {**(rules or {}), **SR.rules_for_config(cfg)}
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda ps: SR.sharding_for(mesh, ps.logical, ps.shape, rules),
        specs, is_leaf=lambda x: isinstance(x, L.PSpec))


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def cache_specs(cfg, batch: int, cache_len: int, *,
                shard_cache_seq: bool = False) -> dict:
    """Spec tree for the decode cache (leaves: (shape, logical, dtype))."""
    kvh, dh, d = cfg.n_kv_heads, cfg.d_head, cfg.d_model
    # decode caches shard their sequence dim over "model" (batch stays on
    # "data"); long-context batch=1 cells widen this to ("data","model")
    seq_ax = "seq_kv_wide" if shard_cache_seq else "seq_kv"
    out: dict = {"pos": ((), (), jnp.int32), "blocks": {}}
    for i, kind in enumerate(cfg.block_pattern):
        mixer, _ = _mixer_ffn(kind)
        c: dict = {}
        nb = (cfg.n_blocks,)
        if mixer in ("attn", "attn_cross"):
            clen = min(cache_len, cfg.window) if cfg.window else cache_len
            c["k"] = (nb + (batch, clen, kvh, dh),
                      (None, "batch", seq_ax, "kv_heads", None), cfg.dtype)
            c["v"] = (nb + (batch, clen, kvh, dh),
                      (None, "batch", seq_ax, "kv_heads", None), cfg.dtype)
        if mixer in ("cross", "attn_cross"):
            klen = cfg.enc_len if cfg.is_encoder_decoder else cfg.img_tokens
            c["ck"] = (nb + (batch, klen, kvh, dh),
                       (None, "batch", None, "kv_heads", None), cfg.dtype)
            c["cv"] = (nb + (batch, klen, kvh, dh),
                       (None, "batch", None, "kv_heads", None), cfg.dtype)
        if mixer == "mamba":
            c["ssm"] = (nb + (batch, cfg.d_inner, cfg.d_state),
                        (None, "batch", "d_inner", None), jnp.float32)
            c["conv"] = (nb + (batch, cfg.d_conv - 1, cfg.d_inner),
                         (None, "batch", None, "d_inner"), cfg.dtype)
        if mixer == "rwkv":
            h = max(1, d // 64)
            dk = d // h
            c["wkv"] = (nb + (batch, h, dk, dk),
                        (None, "batch", "rwkv_heads", None, None), jnp.float32)
            c["tm_x"] = (nb + (batch, d), (None, "batch", None), cfg.dtype)
            c["cm_x"] = (nb + (batch, d), (None, "batch", None), cfg.dtype)
        out["blocks"][f"p{i}"] = c
    return out


def _is_cache_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def cache_zeros(cfg, batch, cache_len, **kw) -> dict:
    return jax.tree.map(lambda s: jnp.zeros(s[0], s[2]),
                        cache_specs(cfg, batch, cache_len, **kw),
                        is_leaf=_is_cache_leaf)


def cache_shape_tree(cfg, mesh, batch, cache_len, rules=None, **kw) -> dict:
    rules = {**(rules or {}), **SR.rules_for_config(cfg)}
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s[0], s[2], sharding=SR.sharding_for(mesh, s[1], s[0], rules)),
        cache_specs(cfg, batch, cache_len, **kw), is_leaf=_is_cache_leaf)


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------

def _batch_axes():
    """Mesh axes carrying the batch dim, from the ambient mesh (if any)."""
    m = jax.sharding.get_abstract_mesh()
    names = m.axis_names if m is not None else ()
    ax = tuple(a for a in ("pod", "data") if a in names)
    return ax if ax else None


def _constrain(x, *axes):
    """with_sharding_constraint that degrades to a no-op off-mesh."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.axis_names:
        return x
    names = set(m.axis_names)
    def ok(a):
        if a is None:
            return True
        return all(x_ in names for x_ in (a if isinstance(a, tuple) else (a,)))
    if not all(ok(a) for a in axes):
        return x
    from jax.sharding import PartitionSpec
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*axes))


def _constrain_act(cfg, x):
    """Layer-boundary activation pin (perf knob ``shard_activations``):
    keeps (B, S, D) batch-sharded so GSPMD gathers the (small) FSDP weight
    shards instead of the (huge) activations."""
    if not cfg.shard_activations:
        return x
    ba = _batch_axes()
    seq = "model" if cfg.attn_seq_shard else None
    return _constrain(x, ba, seq, None)


def _repeat_kv(cfg, k):
    """Repeat kv heads to n_heads for sequence attention: keeps the head dim
    cleanly TP-sharded when kv_heads doesn't divide the model axis."""
    g = cfg.n_heads // cfg.n_kv_heads
    return jnp.repeat(k, g, axis=2) if g > 1 else k


def _attn_seq(cfg, p, x, positions, *, causal=True, make_cache=False,
              cache_len=None):
    q, k, v = L.qkv_project(cfg, p, L.rms_norm(x, p["norm"]))
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    kf, vf = _repeat_kv(cfg, k), _repeat_kv(cfg, v)
    if cfg.attn_seq_shard:
        # context parallelism: queries stay sequence-sharded over 'model',
        # keys/values are gathered (archs whose head count doesn't divide
        # the model axis would otherwise replicate the whole attention)
        ba = _batch_axes()
        q = _constrain(q, ba, "model", None, None)
        kf = _constrain(kf, ba, None, None, None)
        vf = _constrain(vf, ba, None, None, None)
    s = x.shape[1]
    if s > cfg.attn_chunk and s % cfg.attn_chunk == 0:
        o = L.flash_attention(q, kf, vf, causal=causal, window=cfg.window,
                              chunk=cfg.attn_chunk)
    else:
        o = L.attn_naive(q, kf, vf, causal=causal, window=cfg.window)
    out = jnp.einsum("bshd,hde->bse", o, p["wo"])
    if not make_cache:
        return out, None
    clen = max(cache_len or s, s)
    if cfg.window:  # ring buffer holds the last `window` positions
        w = cfg.window
        keep = min(s, w)
        idx = (jnp.arange(s - keep, s)) % w
        ck = jnp.zeros((k.shape[0], w) + k.shape[2:], k.dtype)
        ck = ck.at[:, idx].set(k[:, -keep:])
        cv = jnp.zeros_like(ck).at[:, idx].set(v[:, -keep:])
        return out, (ck, cv)
    if clen > s:  # headroom for subsequent decode steps
        pad = ((0, 0), (0, clen - s), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    return out, (k, v)


def _attn_decode(cfg, p, x1, k_cache, v_cache, pos):
    """x1 (B,1,D); cache (B,S,KV,Dh); pos scalar int32."""
    q, k, v = L.qkv_project(cfg, p, L.rms_norm(x1, p["norm"]))
    ppos = jnp.full((x1.shape[0], 1), pos)
    q = L.rope(q, ppos, cfg.rope_theta)
    k = L.rope(k, ppos, cfg.rope_theta)
    clen = k_cache.shape[1]
    if cfg.window:
        slot = pos % clen
        slot_ids = jnp.arange(clen)
        slot_pos = pos - ((pos - slot_ids) % clen)
        valid = slot_pos >= 0
    else:
        slot = pos
        valid = jnp.arange(clen) <= pos
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, slot, axis=1)
    if cfg.window:
        valid = valid | (slot_ids == slot)
    o = L.attn_decode(q, k_cache, v_cache, valid)
    return jnp.einsum("bshd,hde->bse", o, p["wo"]), k_cache, v_cache


def _cross_attn(cfg, p, x, ext_kv=None, ck=None, cv=None):
    """Cross-attention; ext_kv (B,L,D) at prefill/train, (ck, cv) at decode."""
    norm_w = p.get("norm2", p["norm"])
    xq = L.rms_norm(x, norm_w)
    if ck is None:
        q, ck, cv = L.qkv_project(cfg, p, xq, kv_x=ext_kv)
    else:
        q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"])
        if "qnorm" in p:
            q = L.rms_norm(q, p["qnorm"])
    valid = jnp.ones((ck.shape[1],), bool)
    if q.shape[1] == 1:
        o = L.attn_decode(q, ck, cv, valid)
    else:
        o = L.attn_naive(q, ck, cv, causal=False)
    return jnp.einsum("bshd,hde->bse", o, p["wo"]), ck, cv


def apply_layer(cfg, kind, p, x, *, positions, ext_kv=None, cache=None,
                pos=None, mode="train", cache_len=None):
    """One layer. Returns (x, new_cache, aux)."""
    mixer, ffn = _mixer_ffn(kind)
    aux = jnp.float32(0)
    newc: dict = {}
    if mixer in ("attn", "attn_cross"):
        if mode == "decode":
            o, nk, nv = _attn_decode(cfg, p["attn"], x, cache["k"],
                                     cache["v"], pos)
            newc["k"], newc["v"] = nk, nv
        else:
            o, kv = _attn_seq(cfg, p["attn"], x, positions,
                              make_cache=(mode == "prefill"),
                              cache_len=cache_len)
            if kv is not None:
                newc["k"], newc["v"] = kv
        x = x + o
    if mixer in ("cross", "attn_cross"):
        if mode == "decode":
            o, _, _ = _cross_attn(cfg, p["cross"], x, ck=cache["ck"],
                                  cv=cache["cv"])
            newc["ck"], newc["cv"] = cache["ck"], cache["cv"]
        else:
            o, ck, cv = _cross_attn(cfg, p["cross"], x, ext_kv=ext_kv)
            if mode == "prefill":
                newc["ck"], newc["cv"] = ck, cv
        x = x + o
    if mixer == "mamba":
        xin = L.rms_norm(x, p["mamba"]["norm"])
        if mode == "decode":
            o, h, conv = M.mamba_decode(cfg, p["mamba"], xin, cache["ssm"],
                                        cache["conv"])
            newc["ssm"], newc["conv"] = h, conv
        else:
            o, h = M.mamba_seq(cfg, p["mamba"], xin)
            if mode == "prefill":
                newc["ssm"] = h
                pad = cfg.d_conv - 1
                di = cfg.d_inner
                u = jnp.einsum("bsd,de->bse", xin, p["mamba"]["in_proj"])[
                    ..., :di]
                tail = jnp.pad(u, ((0, 0), (pad, 0), (0, 0)))[:, -pad:]
                newc["conv"] = tail
        x = x + o
    if mixer == "rwkv":
        xin = L.rms_norm(x, p["rwkv"]["tm_norm"])
        if mode == "decode":
            o, s_new, tmx = R.time_mix_decode(cfg, p["rwkv"], xin,
                                              cache["wkv"], cache["tm_x"])
            newc["wkv"], newc["tm_x"] = s_new, tmx
        else:
            o, (s_new, tmx) = R.time_mix_seq(cfg, p["rwkv"], xin)
            if mode == "prefill":
                newc["wkv"], newc["tm_x"] = s_new, tmx
        x = x + o
        xcm = L.rms_norm(x, p["rwkv"]["cm_norm"])
        prev = cache["cm_x"] if mode == "decode" else None
        o, cmx = R.channel_mix(cfg, p["rwkv"], xcm, prev)
        if mode in ("decode", "prefill"):
            newc["cm_x"] = cmx
        x = x + o
    if ffn == "mlp":
        x = x + L.mlp(p["mlp"], L.rms_norm(x, p["mlp"]["norm"]),
                      bf16_reduce=cfg.bf16_reduce, batch_axes=_batch_axes())
    elif ffn == "moe":
        o, a = MOE.moe_ffn(cfg, p["moe"],
                           L.rms_norm(x, p["moe"]["norm"]))
        x = x + o
        aux = aux + a
    return x, newc, aux


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def _encoder(cfg, params, frames):
    """Whisper-style encoder over precomputed frame embeddings (stub
    frontend): bidirectional attention blocks."""
    b, s, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = frames

    def block(x, p):
        o, _ = _attn_seq(cfg, p["attn"], x, positions, causal=False)
        x = x + o
        x = x + L.mlp(p["mlp"], L.rms_norm(x, p["mlp"]["norm"]))
        return x, None

    if cfg.scan_layers:
        x, _ = jax.lax.scan(block, x, params["encoder"]["blocks"])
    else:
        for i in range(cfg.n_enc_layers):
            bp = jax.tree.map(lambda a: a[i], params["encoder"]["blocks"])
            x, _ = block(x, bp)
    return L.rms_norm(x, params["encoder"]["norm"])


def forward(cfg, params, tokens, *, ext_embed=None, mode="train",
            cache=None, cache_len=None):
    """tokens (B,S) int32; ext_embed (B,L,D) — image patches / audio frames.

    Returns (logits, new_cache | None, aux_loss)."""
    b, s = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    ext_kv = None
    if cfg.is_encoder_decoder and mode != "decode":
        ext_kv = _encoder(cfg, params, ext_embed)
    elif cfg.img_tokens and mode != "decode":
        ext_kv = ext_embed
    if mode == "decode":
        pos = cache["pos"]
        positions = jnp.full((b, 1), pos)
    else:
        pos = None
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    npat = len(cfg.block_pattern)

    def superblock(x_aux, xs):
        x, aux = x_aux
        bp, bc = xs
        newc = {}
        x = _constrain_act(cfg, x)
        for i, kind in enumerate(cfg.block_pattern):
            c_i = bc[f"p{i}"] if bc is not None else None
            x, nc, a = apply_layer(cfg, kind, bp[f"p{i}"], x,
                                   positions=positions, ext_kv=ext_kv,
                                   cache=c_i, pos=pos, mode=mode,
                                   cache_len=cache_len)
            aux = aux + a
            newc[f"p{i}"] = nc
        return (x, aux), newc

    body = superblock
    if cfg.remat and mode == "train":
        body = jax.checkpoint(superblock)

    aux0 = jnp.float32(0)
    bc = cache["blocks"] if cache is not None else None
    if cfg.scan_layers:
        (x, aux), newblocks = jax.lax.scan(body, (x, aux0),
                                           (params["blocks"], bc))
    else:  # unrolled (used by the dry-run per-block cost extrapolation)
        carry = (x, aux0)
        percall = []
        for i in range(cfg.n_blocks):
            bp_i = jax.tree.map(lambda a: a[i], params["blocks"])
            bc_i = jax.tree.map(lambda a: a[i], bc) if bc is not None else None
            carry, nc = body(carry, (bp_i, bc_i))
            percall.append(nc)
        x, aux = carry
        newblocks = jax.tree.map(lambda *xs: jnp.stack(xs), *percall) \
            if percall and jax.tree.leaves(percall[0]) else {}

    if mode == "prefill":
        # serving only consumes the last position's logits; skipping the
        # full (B, S, V) head drops its flops/collectives (§Perf)
        x = x[:, -1:]
    x = L.rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]).astype(jnp.float32)
    new_cache = None
    if mode in ("prefill", "decode"):
        newpos = (cache["pos"] + 1) if mode == "decode" else jnp.int32(s)
        new_cache = {"pos": newpos, "blocks": newblocks}
    return logits, new_cache, aux
