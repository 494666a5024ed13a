"""Memory-optimal attention: custom-VJP online-softmax with recompute backward.

JAX autodiff of the online-softmax scan in ``layers.chunked_attention`` saves
per-chunk residuals (probability blocks and accumulator carries) — O(S²/chunk)
memory per layer, which dominated the baseline train_4k dry-runs (§Perf).
This implementation saves only (q, k, v, out, lse) and recomputes probability
blocks in the backward pass from the logsumexp — the FlashAttention recipe in
pure JAX.

Sharding: the scan runs over KV chunks only; the full q-sequence axis stays a
plain tensor dimension, so it can be sharded across the model axis
(``shard_axis='model'``) when attention heads don't divide it — this removed
the per-chunk score all-reduces that dominated the baseline collective term
(65536 × 640 MB for qwen2.5-32b prefill; see EXPERIMENTS.md §Perf). K/V are
small (KV-head count × hd) and are left to replicate per layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _maybe_shard(x, spec):
    """Pin ``x`` to ``spec`` on the ambient mesh; a no-op where no mesh is
    set (plain single-device calls) or it lacks an auto axis the spec names."""
    if spec is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    named = {a for s in spec if s is not None
             for a in (s if isinstance(s, tuple) else (s,))}
    if mesh.empty or not named <= set(mesh.auto_axes):
        return x
    return lax.with_sharding_constraint(x, P(*spec))


def _pad_to(x, n, axis):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, pad) if n != x.shape[axis] else x


def _mask(qpos, kpos, causal, window, kv_valid):
    m = kpos[None, :] < kv_valid
    if causal:
        m = m & (kpos[None, :] <= qpos[:, None])
    if window:
        m = m & (kpos[None, :] > (qpos[:, None] - window))
    return m  # (Sq, kc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    q_offset: int = 0, kv_chunk: int = 1024,
                    shard_axis: str = "", batch_axis: str = ""):
    """q: (B,Sq,H,hd), k/v: (B,Skv,KV,hd) -> (B,Sq,H,hd). GQA supported.

    ``shard_axis``: mesh axis to shard the q-sequence dimension over;
    ``batch_axis``: mesh axis the batch dim stays sharded over (inference) —
    omitting it would force batch replication (measured §Perf iteration 2)."""
    out, _ = _fwd_impl(q, k, v, causal, window, q_offset, kv_chunk, shard_axis,
                       batch_axis)
    return out


def _q_spec(shard_axis, batch_axis=""):
    if not shard_axis and not batch_axis:
        return None
    return (batch_axis or None, None, None, shard_axis or None, None)


def _fwd_impl(q, k, v, causal, window, q_offset, kv_chunk, shard_axis,
              batch_axis=""):
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    kc = min(kv_chunk, Skv)
    Skp = -(-Skv // kc) * kc
    nk = Skp // kc
    qh = q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4)  # (B,KV,G,Sq,hd)
    qh = _maybe_shard(qh, _q_spec(shard_axis, batch_axis))
    kp = _pad_to(k, Skp, 1).reshape(B, nk, kc, KV, hd)
    vp = _pad_to(v, Skp, 1).reshape(B, nk, kc, KV, hd)
    qpos = q_offset + jnp.arange(Sq)

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = _maybe_shard(jnp.zeros((B, KV, G, Sq, hd), jnp.float32),
                      _q_spec(shard_axis, batch_axis))

    def kv_body(carry, ki):
        m, l, acc = carry
        kpos = ki * kc + jnp.arange(kc)
        s = jnp.einsum("bkgqd,bskd->bkgqs", qh, kp[:, ki],
                       preferred_element_type=jnp.float32) * scale
        msk = _mask(qpos, kpos, causal, window, Skv)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vp.dtype), vp[:, ki],
                        preferred_element_type=jnp.float32)
        return (m_new, l_new, acc * corr[..., None] + pv), None

    (m, l, acc), _ = lax.scan(kv_body, (m0, l0, a0), jnp.arange(nk))
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,KV,G,Sq,hd)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)
    return out, lse


def _fwd(q, k, v, causal, window, q_offset, kv_chunk, shard_axis, batch_axis):
    out, lse = _fwd_impl(q, k, v, causal, window, q_offset, kv_chunk, shard_axis,
                         batch_axis)
    return out, (q, k, v, out, lse)


def _bwd(causal, window, q_offset, kv_chunk, shard_axis, batch_axis, res, dout):
    q, k, v, out, lse = res
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    kc = min(kv_chunk, Skv)
    Skp = -(-Skv // kc) * kc
    nk = Skp // kc
    qh = q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4)
    qh = _maybe_shard(qh, _q_spec(shard_axis, batch_axis))
    doh = dout.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4).astype(jnp.float32)
    doh = _maybe_shard(doh, _q_spec(shard_axis, batch_axis))
    oh = out.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4).astype(jnp.float32)
    kp = _pad_to(k, Skp, 1).reshape(B, nk, kc, KV, hd)
    vp = _pad_to(v, Skp, 1).reshape(B, nk, kc, KV, hd)
    qpos = q_offset + jnp.arange(Sq)
    Drow = jnp.sum(doh * oh, axis=-1)  # (B,KV,G,Sq)

    dq0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    dq0 = _maybe_shard(dq0, _q_spec(shard_axis, batch_axis))

    def kv_body(dq_acc, ki):
        kpos = ki * kc + jnp.arange(kc)
        s = jnp.einsum("bkgqd,bskd->bkgqs", qh, kp[:, ki],
                       preferred_element_type=jnp.float32) * scale
        msk = _mask(qpos, kpos, causal, window, Skv)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])  # recomputed probabilities
        dp = jnp.einsum("bkgqd,bskd->bkgqs", doh.astype(v.dtype), vp[:, ki],
                        preferred_element_type=jnp.float32)
        ds = p * (dp - Drow[..., None]) * scale
        dq_c = jnp.einsum("bkgqs,bskd->bkgqd", ds.astype(k.dtype), kp[:, ki],
                          preferred_element_type=jnp.float32)
        dk_c = jnp.einsum("bkgqs,bkgqd->bskd", ds.astype(q.dtype), qh,
                          preferred_element_type=jnp.float32)
        dv_c = jnp.einsum("bkgqs,bkgqd->bskd", p.astype(jnp.float32), doh,
                          preferred_element_type=jnp.float32)
        return dq_acc + dq_c, (dk_c, dv_c)

    dq, (dks, dvs) = lax.scan(kv_body, dq0, jnp.arange(nk))
    dq = dq.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, Skp, KV, hd)[:, :Skv]
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, Skp, KV, hd)[:, :Skv]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_fwd, _bwd)
