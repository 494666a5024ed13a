"""Primitive layers: norms, RoPE, chunked (flash-style) attention, MLPs.

Attention is written as an online-softmax scan over KV chunks so that the
lowered HLO never materializes an (S, S) score matrix — this is what keeps the
train_4k / prefill_32k dry-runs inside the per-chip HBM budget.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------- norms


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * lax.rsqrt(var + eps) * scale + bias).astype(dtype)


def apply_norm(x, p, kind: str):
    if kind == "layernorm":
        return layer_norm(x, p["scale"], p["bias"])
    return rms_norm(x, p["scale"])


def group_norm_heads(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Per-head groupnorm used by RWKV time-mix output. x: (..., H, hd)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * lax.rsqrt(var + eps) * scale).astype(dtype)


# ---------------------------------------------------------------- RoPE


def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """positions: (...,) int -> cos/sin of shape (..., head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (S, hd//2) or broadcastable."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    # broadcast cos/sin over batch/head axes: (S, half) -> (1, S, 1, half)
    while cos.ndim < x1.ndim:
        cos, sin = cos[None], sin[None]
        if cos.ndim == x1.ndim - 1:  # insert head axis before last
            cos = jnp.expand_dims(cos, -2)
            sin = jnp.expand_dims(sin, -2)
            break
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


# ---------------------------------------------------------------- attention

NEG_INF = -1e30


def _attn_chunk(qc, kc, vc, qpos, kpos, *, causal, window, scale, m, l, acc,
                bias=None, kv_len=None):
    """One online-softmax update. qc: (B,Q,KV,G,hd) kc/vc: (B,S,KV,hd)."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", qc, kc, preferred_element_type=jnp.float32)
    s = s * scale
    if bias is not None:
        s = s + bias
    mask = kpos[None, :] >= 0  # also masks padded kv slots (kpos = INTMAX-tagged)
    if kv_len is not None:
        mask = kpos[None, :] < kv_len
    if causal:
        mask = mask & (kpos[None, :] <= qpos[:, None])
    if window:
        mask = mask & (kpos[None, :] > (qpos[:, None] - window))
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vc.dtype), vc,
                    preferred_element_type=jnp.float32)
    acc_new = acc * corr[..., None] + pv
    return m_new, l_new, acc_new


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Flash-style attention. q: (B,Sq,H,hd), k/v: (B,Skv,KV,hd) -> (B,Sq,H,hd).

    GQA via reshaping q heads into (KV, G). Memory is O(chunk^2), not O(S^2).
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    qc_n = min(q_chunk, Sq)
    kc_n = min(kv_chunk, Skv)
    # pad to multiples
    Sq_p = -(-Sq // qc_n) * qc_n
    Skv_p = -(-Skv // kc_n) * kc_n
    qp = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Skv_p - Skv), (0, 0), (0, 0)))
    qp = qp.reshape(B, Sq_p // qc_n, qc_n, KV, G, hd)
    kp = kp.reshape(B, Skv_p // kc_n, kc_n, KV, hd)
    vp = vp.reshape(B, Skv_p // kc_n, kc_n, KV, hd)
    kv_valid = Skv  # mask padded kv positions via kpos >= Skv

    def q_body(_, qi):
        qcb = qp[:, qi]  # (B, qc, KV, G, hd)
        qpos = q_offset + qi * qc_n + jnp.arange(qc_n)
        m0 = jnp.full((B, KV, G, qc_n), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qc_n), jnp.float32)
        a0 = jnp.zeros((B, KV, G, qc_n, hd), jnp.float32)

        def kv_body(carry, ki):
            m, l, acc = carry
            kpos = ki * kc_n + jnp.arange(kc_n)
            m, l, acc = _attn_chunk(
                qcb, kp[:, ki], vp[:, ki], qpos, kpos,
                causal=causal, window=window, scale=scale, m=m, l=l, acc=acc,
                kv_len=kv_valid)
            return (m, l, acc), None

        (m, l, acc), _ = lax.scan(kv_body, (m0, l0, a0), jnp.arange(Skv_p // kc_n))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B, KV, G, qc, hd) -> (B, qc, KV*G, hd)
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, qc_n, H, hd)
        return None, out.astype(q.dtype)

    _, outs = lax.scan(q_body, None, jnp.arange(Sq_p // qc_n))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq_p, H, hd)
    return out[:, :Sq]


def decode_attention(
    q: jax.Array,  # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, S, KV, hd)
    v_cache: jax.Array,
    length_mask: Optional[jax.Array] = None,  # (B, S) bool, True = valid
) -> jax.Array:
    """Single-token attention against a KV cache (B,1,H,hd)."""
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    qh = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qh, k_cache, preferred_element_type=jnp.float32)
    s = s * scale
    if length_mask is not None:
        s = jnp.where(length_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------- MLP


def mlp(x: jax.Array, p: dict, act: str) -> jax.Array:
    if act == "swiglu":
        h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
    else:
        h = jax.nn.gelu(x @ p["w1"] + p.get("b1", 0))
    out = h @ p["w2"]
    if "b2" in p:
        out = out + p["b2"]
    return out
