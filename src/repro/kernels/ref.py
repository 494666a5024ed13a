"""Pure-jnp oracles for the aggregation kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# full f32 matmuls, as in the kernels (a TPU's default is one bf16 pass)
_F32 = jax.lax.Precision.HIGHEST


def cwmed_ref(x: jnp.ndarray) -> jnp.ndarray:
    """x: (m, d) -> (d,) coordinate-wise median (float32)."""
    return jnp.median(x.astype(jnp.float32), axis=0)


def cwtm_ref(x: jnp.ndarray, trim) -> jnp.ndarray:
    """x: (m, d) -> (d,) trimmed mean dropping `trim` lowest/highest.

    ``trim`` may be a Python int or a traced int32 scalar (the uniform
    theta path of ``core.agg_engine``): one masked sorted-sum form serves
    both, so static and traced calls are bitwise identical by construction
    — a sliced ``xs[trim:m-trim].mean(0)`` would reduce over a different
    tree shape and drift at ULP level."""
    m = x.shape[0]
    xs = jnp.sort(x.astype(jnp.float32), axis=0)
    i = jnp.arange(m)[:, None]
    keep = ((i >= trim) & (i < m - trim)).astype(jnp.float32)
    return (xs * keep).sum(0) / jnp.asarray(m - 2 * trim, jnp.float32)


def pairwise_sqdist_ref(x: jnp.ndarray) -> jnp.ndarray:
    """x: (m, d) -> (m, m) squared L2 distances (float32)."""
    x = x.astype(jnp.float32)
    sq = jnp.sum(x * x, axis=1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * jnp.matmul(x, x.T, precision=_F32)
    return jnp.maximum(d2, 0.0)


def cross_sqdist_ref(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """x: (m, d), y: (k, d) -> (m, k) squared L2 distances (float32).

    Direct subtraction, NOT the ||x||²+||y||²−2x·y expansion: Weiszfeld
    iterates sit close to the points, where the expansion cancels
    catastrophically in f32 (distances ~1e-7·||x||² round to 0 and GeoMed
    degenerates to a mean). k is tiny (1 for GeoMed), so the (m, k, d)
    broadcast is cheap."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    d2 = jnp.sum(jnp.square(x[:, None, :] - y[None, :, :]), axis=-1)
    return jnp.maximum(d2, 0.0)


def weighted_combine_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """x: (m, d), w: (k, m) -> (k, d) = w @ x (float32)."""
    return jnp.matmul(w.astype(jnp.float32), x.astype(jnp.float32),
                      precision=_F32)
