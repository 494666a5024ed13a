"""GShard/Switch-style Mixture-of-Experts FFN with capacity-based dispatch.

Dense einsum dispatch (tokens x experts x capacity one-hots) — the standard
TPU-friendly formulation: expert dim shards over the data axis
(expert-parallel) and the ff dim over the model axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _capacity(tokens: int, top_k: int, factor: float, E: int) -> int:
    """Per-expert token capacity ⌊tokens·k·factor/E⌋, nudged so f64
    representation error cannot truncate an exact boundary one token short
    (int(0.3 * 10) == 2) — the local twin of ``agg_engine.count_floor``
    (models/ stays import-independent of core/)."""
    # jaxlint: disable=JXL003 -- sanctioned nudged-floor helper, see docstring
    return max(1, math.floor(tokens * top_k * factor / E + 1e-5))


def _topk_dispatch(probs: jax.Array, top_k: int, capacity: int):
    """probs: (N, E) -> dispatch (N, E, C) float, combine (N, E, C) float, aux."""
    N, E = probs.shape
    idx = jax.lax.top_k(probs, top_k)[1]  # (N, k) indices only
    # gates re-read probs via one-hots rather than using top_k's value
    # output: the transpose is then a matmul, not a scatter
    gates = jnp.einsum("nke,ne->nk", jax.nn.one_hot(idx, E, dtype=probs.dtype),
                       probs)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((N, E, capacity), probs.dtype)
    combine = jnp.zeros((N, E, capacity), probs.dtype)
    counts = jnp.zeros((E,), jnp.int32)
    frac_dispatched = jnp.zeros((E,), jnp.float32)
    for k in range(top_k):
        m = jax.nn.one_hot(idx[:, k], E, dtype=jnp.int32)  # (N, E)
        pos = jnp.cumsum(m, axis=0) - m + counts[None, :]  # position within expert
        counts = counts + m.sum(0)
        keep = (pos < capacity) & (m > 0)
        oh_pos = jax.nn.one_hot(pos, capacity, dtype=probs.dtype)  # (N, E, C)
        slot = keep.astype(probs.dtype)[..., None] * oh_pos
        dispatch = dispatch + slot
        combine = combine + slot * gates[:, k][:, None, None]
        frac_dispatched = frac_dispatched + m.astype(jnp.float32).mean(0)
    # load-balance aux loss (Switch/GShard): E * sum_e mean_prob_e * mean_dispatch_e
    aux = E * jnp.sum(probs.astype(jnp.float32).mean(0) * frac_dispatched / max(top_k, 1))
    return dispatch, combine, aux


def _moe_group(xf: jax.Array, p: dict, top_k: int, capacity: int, act: str,
               expert_shard: str = ""):
    """One token group through the experts. xf: (N, D) -> (N, D), aux.

    ``expert_shard``: mesh axis to pin the expert dim of the dispatched
    activations to (expert parallelism) — without the constraint XLA may
    all-gather the expert weights instead (§Perf iteration 3)."""
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine, aux = _topk_dispatch(probs, top_k, capacity)
    dispatch = dispatch.astype(xf.dtype)
    combine = combine.astype(xf.dtype)

    def pin(t):
        if not expert_shard:
            return t
        from repro.models.flash import _maybe_shard
        return _maybe_shard(t, (expert_shard,) + (None,) * (t.ndim - 1))

    xs = pin(jnp.einsum("nec,nd->ecd", dispatch, xf))  # (E, C, D)
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, p["we1"]))
        h = h * jnp.einsum("ecd,edf->ecf", xs, p["we3"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xs, p["we1"]))
    ys = pin(jnp.einsum("ecf,efd->ecd", pin(h), p["we2"]))  # (E, C, D)
    return jnp.einsum("nec,ecd->nd", combine, ys), aux


def moe_ffn(x: jax.Array, p: dict, *, top_k: int, capacity_factor: float,
            act: str = "swiglu", token_group: int = 0,
            expert_shard: str = "") -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D). p: router (D,E), we1/we3 (E,D,F), we2 (E,F,D).

    ``token_group`` > 0 routes tokens in independent groups of that size
    (GShard-style grouping): the (N, E, C) dispatch one-hots are then
    O(group·E·C_group) instead of O(N·E·C) ~ N² — essential at prefill scale.
    Returns (out (B,S,D), aux_loss scalar).
    """
    B, S, D = x.shape
    E = p["router"].shape[1]
    N = B * S
    xf = x.reshape(N, D)
    if S == 1:  # decode: tiny token count — guarantee zero drops
        out, aux = _moe_group(xf, p, top_k, N, act, expert_shard)
        return out.reshape(B, S, D), aux
    if token_group and N > token_group and N % token_group == 0:
        g = N // token_group
        capacity = _capacity(token_group, top_k, capacity_factor, E)
        xg = xf.reshape(g, token_group, D)
        # vmap (not scan): keeps the group axis a shardable tensor dim
        out, auxs = jax.vmap(
            lambda xc: _moe_group(xc, p, top_k, capacity, act, expert_shard))(xg)
        return out.reshape(B, S, D), auxs.mean()
    capacity = _capacity(N, top_k, capacity_factor, E)
    out, aux = _moe_group(xf, p, top_k, capacity, act, expert_shard)
    return out.reshape(B, S, D), aux
