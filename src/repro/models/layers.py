"""Core transformer layers: norms, RoPE, GQA attention (naive / blockwise
flash with custom_vjp / decode), SwiGLU MLP, and the ParamSpec machinery
that carries logical sharding axes for every weight."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


class PSpec(NamedTuple):
    """Declarative parameter: shape + logical sharding axes + init."""
    shape: tuple
    logical: tuple
    init: str = "normal"  # normal | zeros | ones
    scale: float = 1.0


def init_param(key, spec: PSpec, dtype):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    fan_in = spec.shape[0] if len(spec.shape) > 1 else max(spec.shape[-1], 1)
    std = spec.scale / np.sqrt(fan_in)
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope(x, positions, theta: float):
    """x: (..., S, H, D) with rotary over D; positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention — parameter specs
# ---------------------------------------------------------------------------

def attn_specs(cfg, cross: bool = False) -> dict:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    s = {
        "norm": PSpec((d,), (None,), "ones"),
        "wq": PSpec((d, h, dh), ("embed", "heads", None)),
        "wk": PSpec((d, kv, dh), ("embed", "kv_heads", None)),
        "wv": PSpec((d, kv, dh), ("embed", "kv_heads", None)),
        "wo": PSpec((h, dh, d), ("heads", None, "fsdp"), scale=1.0),
    }
    if cfg.qkv_bias and not cross:
        s["bq"] = PSpec((h, dh), ("heads", None), "zeros")
        s["bk"] = PSpec((kv, dh), ("kv_heads", None), "zeros")
        s["bv"] = PSpec((kv, dh), ("kv_heads", None), "zeros")
    if cfg.qk_norm:
        s["qnorm"] = PSpec((dh,), (None,), "ones")
        s["knorm"] = PSpec((dh,), (None,), "ones")
    return s


def qkv_project(cfg, p, x, kv_x=None):
    """x: (B,S,D) -> q (B,S,H,Dh), k/v (B,Skv,KV,Dh). kv_x for cross-attn."""
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if "qnorm" in p:
        q = rms_norm(q, p["qnorm"])
        k = rms_norm(k, p["knorm"])
    return q, k, v


# ---------------------------------------------------------------------------
# attention — naive reference (small S; also the flash test oracle)
# ---------------------------------------------------------------------------

def attn_naive(q, k, v, *, causal: bool, window=None, q_offset: int = 0):
    """q: (B,Sq,H,D), k/v: (B,Sk,KV,D) — GQA by head repetition."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, d)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    s = s / np.sqrt(d)
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", a.astype(v.dtype), v)
    return o.reshape(b, sq, h, d)


# ---------------------------------------------------------------------------
# attention — blockwise "flash" with custom_vjp (O(S) memory)
# ---------------------------------------------------------------------------

def _flash_fwd_impl(q, k, v, *, causal, window, chunk):
    b, sq, kvh, g, d = q.shape
    sk = k.shape[1]
    nchunks = sk // chunk
    scale = 1.0 / np.sqrt(d)
    qpos = jnp.arange(sq)

    def step(carry, ci):
        acc, m, l = carry
        kc = jax.lax.dynamic_slice_in_dim(k, ci * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, ci * chunk, chunk, axis=1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, kc).astype(jnp.float32) * scale
        kpos = ci * chunk + jnp.arange(chunk)
        mask = jnp.ones((sq, chunk), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # explicit zero for masked entries: when a whole chunk is masked for a
        # row, s == m_new == NEG_INF and exp(s - m_new) would be 1, not 0
        p = jnp.where(mask[None, None, None], jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(v.dtype), vc).astype(jnp.float32)
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, kvh, g, sq, d), jnp.float32)
    m0 = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0),
                                  jnp.arange(nchunks))
    l_safe = jnp.where(l == 0, 1.0, l)
    out = (acc / l_safe[..., None]).astype(q.dtype)  # (b,kv,g,sq,d)
    lse = m + jnp.log(l_safe)
    return out, lse


def _flash_bwd_impl(q, k, v, out, lse, dout, *, causal, window, chunk):
    b, sq, kvh, g, d = q.shape
    sk = k.shape[1]
    nchunks = sk // chunk
    scale = 1.0 / np.sqrt(d)
    qpos = jnp.arange(sq)
    # D_i = rowsum(dout * out)
    delta = jnp.einsum("bkgqd,bkgqd->bkgq", dout.astype(jnp.float32),
                       out.astype(jnp.float32))

    def step(dq, ci):
        kc = jax.lax.dynamic_slice_in_dim(k, ci * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, ci * chunk, chunk, axis=1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, kc).astype(jnp.float32) * scale
        kpos = ci * chunk + jnp.arange(chunk)
        mask = jnp.ones((sq, chunk), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jnp.where(mask[None, None, None],
                      jnp.exp(s - lse[..., None]), 0.0)  # (b,kv,g,q,s)
        dp = jnp.einsum("bkgqd,bskd->bkgqs", dout.astype(jnp.float32),
                        vc.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bkgqs,bskd->bqkgd", ds.astype(q.dtype), kc)
        dkc = jnp.einsum("bkgqs,bqkgd->bskd", ds.astype(q.dtype), q)
        dvc = jnp.einsum("bkgqs,bkgqd->bskd", p.astype(q.dtype),
                         dout)
        return dq, (dkc, dvc)

    dq0 = jnp.zeros_like(q)
    dq, (dk, dv) = jax.lax.scan(step, dq0, jnp.arange(nchunks))
    dk = dk.swapaxes(0, 1).reshape(b, sk, kvh, d)
    dv = dv.swapaxes(0, 1).reshape(b, sk, kvh, d)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, window, chunk):
    out, _ = _flash_fwd_impl(q, k, v, causal=causal, window=window,
                             chunk=chunk)
    return out


def _flash_fwd(q, k, v, causal, window, chunk):
    out, lse = _flash_fwd_impl(q, k, v, causal=causal, window=window,
                               chunk=chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, chunk, res, dout):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, out, lse, dout,
                                 causal=causal, window=window, chunk=chunk)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal=True, window=None, chunk=1024):
    """Blockwise attention, O(S) memory: q (B,S,H,D), k/v (B,S,KV,D)."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, sq, kvh, h // kvh, d)
    sk = k.shape[1]
    chunk = min(chunk, sk)
    if sk % chunk:  # pad kv to a chunk multiple; masked out via positions
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if not causal:  # causal mask already kills padded keys (kpos > qpos)
            raise NotImplementedError("pad only supported for causal")
    out = _flash(qg, k, v, causal, window, chunk)  # (b,kv,g,sq,d)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)


def attn_decode(q, k_cache, v_cache, valid_mask):
    """Single-token attention over a cache.

    q: (B,1,H,D); k/v_cache: (B,S,KV,D); valid_mask: (B,S) or (S,).
    Softmax is written max/sum-decomposed so a cache sharded along S lowers
    to psum-style collectives under GSPMD (long-context sequence
    parallelism)."""
    b, _, h, d = q.shape
    kvh = k_cache.shape[2]
    qg = q.reshape(b, kvh, h // kvh, d)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32)
    s = s / np.sqrt(d)
    if valid_mask.ndim == 1:
        valid_mask = valid_mask[None]
    s = jnp.where(valid_mask[:, None, None, :], s, NEG_INF)
    m = jax.lax.stop_gradient(s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    o = jnp.einsum("bkgs,bskd->bkgd", (p / l).astype(v_cache.dtype), v_cache)
    return o.reshape(b, 1, h, d)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def tp_matmul_bf16reduce(x, w, *, batch_axes):
    """Tensor-parallel contraction with an explicit **bf16** cross-device
    combine: x (..., F/tp) x w (F/tp, D) -> psum_bf16(..., D).

    GSPMD keeps partial-dot accumulators in f32 and all-reduces them at
    twice the wire bytes; this shard_map computes the local partial, rounds
    to bf16, and psums the rounded value (Megatron-style bf16 all-reduce).
    Falls back to a plain matmul when no 'model' axis is present."""
    import jax
    from jax.sharding import PartitionSpec as P
    m = jax.sharding.get_abstract_mesh()
    if m is None or "model" not in m.axis_names:
        return x @ w
    ba = tuple(a for a in (batch_axes or ()) if a in m.axis_names) or None

    def local(xl, wl):
        part = (xl @ wl).astype(jnp.bfloat16)
        return jax.lax.psum(part, "model")

    nd = x.ndim
    in_x = P(*((ba,) + (None,) * (nd - 2) + ("model",)))
    in_w = P("model", None)
    out = P(*((ba,) + (None,) * (nd - 1)))
    return jax.shard_map(local, mesh=None, in_specs=(in_x, in_w),
                         out_specs=out, check_vma=False)(x, w)


def mlp_specs(cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "norm": PSpec((d,), (None,), "ones"),
        "w_gate": PSpec((d, f), ("fsdp", "ffn")),
        "w_up": PSpec((d, f), ("fsdp", "ffn")),
        "w_down": PSpec((f, d), ("ffn", "fsdp")),
    }


def mlp(p, x, bf16_reduce: bool = False, batch_axes=None):
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    if bf16_reduce:
        return tp_matmul_bf16reduce(h, p["w_down"], batch_axes=batch_axes)
    return h @ p["w_down"]
