"""Pallas TPU kernel: fused one-pass aggregation over tiled (m, d) blocks.

THE kernel of the aggregation engine (DESIGN.md §4): every per-round
primitive — coordinate-wise trim/median selection (formerly ``cwmed.py``),
pairwise-distance accumulation (formerly ``pairwise.py``) and weighted
combine (formerly ``combine.py``) — is a *stage* of one kernel body that
streams each (m, TILE_D) block of the worker stack through VMEM exactly
once. A call requesting several stages pays one HBM read of the stack
instead of one per ``pallas_call``; composites chain stages in-register:
the mix+reduce form (NNM's hot step) multiplies the mixing matrix into the
tile and sorts the *mixed* rows without the (m, d) mixed stack ever
existing in HBM.

Layout (unchanged from the subsumed kernels): m is tiny (9–32 workers),
d is huge, so the grid walks d tiles. Per step:

  * load x: the (m, TILE_D) block, cast to f32 — the single stack read;
  * [pairwise]  (m, TILE_D) × (TILE_D, m) MXU matmul, sq-norm/gram partials
    accumulated straight into the (m, m) output block (output revisited
    across the sequential TPU grid ⇒ accumulation is safe);
  * [mix]       (k, m) × (m, TILE_D) MXU matmul y = w @ x (k ≤ m);
  * [combine]   y written to the (k, TILE_D) output tile;
  * [reduce]    the rows of y (of x when no weights) sorted with a bitonic
    network (min/max row swaps — no data-dependent control flow, VPU
    friendly; the row count padded to a power of two with +inf rows) and
    the median / trimmed mean / mean emitted as a (TILE_D,) tile. The trim
    count is a Python int (statically sliced) or a traced int32 riding
    along as a (1,) operand (per-row masks — one compiled kernel serves
    every trim value; scalars belong in SMEM on real TPUs, a rank-1 int
    block is the interpret-mode-portable equivalent this CPU-validated
    repo can test).

``cross_sqdist`` (GeoMed's Weiszfeld distances) keeps its own two-operand
streaming kernel below: it is the one primitive that cannot share the
stack read (it consumes x *and* the iterate z) and its direct-subtraction
numerics must not go through the gram expansion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_INF = 3.0e38  # python float: becomes a kernel-local constant, not a capture

REDUCE_MODES = ("med", "tm", "mean")

# full f32 matmuls: a TPU's default single bf16 pass would cost the gram
# expansion of the pairwise stage most of its digits
_F32 = jax.lax.Precision.HIGHEST


def _bitonic_sort_rows(rows):
    """Sort a list of (TILE_D,) f32 rows ascending, element-wise (each
    coordinate sorted independently across rows). len(rows) must be a power
    of two. Returns the sorted list."""
    n = len(rows)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            for i in range(n):
                l = i ^ j
                if l > i:
                    up = (i & k) == 0
                    a, b = rows[i], rows[l]
                    lo = jnp.minimum(a, b)
                    hi = jnp.maximum(a, b)
                    rows[i] = lo if up else hi
                    rows[l] = hi if up else lo
            j //= 2
        k *= 2
    return rows


def _sorted_rows(rows):
    """Pad a row list to the next power of two with +inf rows (so the
    network is shape-static; statistics index only the valid prefix) and
    sort."""
    n = len(rows)
    np2 = 1 << (n - 1).bit_length()
    rows = list(rows) + [jnp.full_like(rows[0], _INF) for _ in range(np2 - n)]
    return _bitonic_sort_rows(rows)


def _reduce_tile(rows, mode: str, trim, t_ref):
    """Element-wise reduce a list of n f32 rows to one row: ``med`` /
    ``tm`` (static ``trim`` slice, or per-row masks against the traced
    ``t_ref[0]``) / ``mean``. The accumulation orders replicate the
    subsumed cwmed.py kernels exactly, so delegating callers keep their
    numerics."""
    n = len(rows)
    if mode == "mean":
        acc = rows[0]
        for r in rows[1:]:
            acc = acc + r
        return acc / float(n)
    srt = _sorted_rows(rows)
    if mode == "med":
        if n % 2:
            return srt[n // 2]
        return 0.5 * (srt[n // 2 - 1] + srt[n // 2])
    if t_ref is None:  # static trim
        keep = srt[trim:n - trim] if trim else srt[:n]
        acc = keep[0]
        for r in keep[1:]:
            acc = acc + r
        return acc / float(len(keep))
    t = t_ref[0]
    acc = jnp.zeros_like(srt[0])
    for i in range(n):
        live = jnp.logical_and(i >= t, i < n - t)
        acc = acc + jnp.where(live, srt[i], 0.0)
    return acc / (n - 2 * t).astype(jnp.float32)


def _fused_kernel(*refs, m: int, mode, trim: int, has_w: bool, has_t: bool,
                  pairwise: bool, combine: bool):
    it = iter(refs)
    w_ref = next(it) if has_w else None
    x_ref = next(it)
    t_ref = next(it) if has_t else None
    red_ref = next(it) if mode else None
    pw_ref = next(it) if pairwise else None
    comb_ref = next(it) if combine else None

    x = x_ref[...].astype(jnp.float32)  # (m, tile): the ONE stack read

    if pairwise:
        i = pl.program_id(0)
        gram = jax.lax.dot_general(x, x, (((1,), (1,)), ((), ())),
                                   precision=_F32,
                                   preferred_element_type=jnp.float32)
        sq = jnp.diagonal(gram)
        part = sq[:, None] + sq[None, :] - 2.0 * gram

        @pl.when(i == 0)
        def _init():
            pw_ref[...] = part

        @pl.when(i != 0)
        def _acc():
            pw_ref[...] += part

    y = x
    if has_w:
        w = w_ref[...].astype(jnp.float32)  # (k, m)
        y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32)
        if combine:
            comb_ref[...] = y

    if mode:
        rows = [y[i, :] for i in range(y.shape[0])]
        red_ref[...] = _reduce_tile(rows, mode, trim, t_ref)


def fused_pass(x: jax.Array, *, w=None, reduce=None, trim=0,
               pairwise: bool = False, combine: bool = False,
               tile_d: int = 2048, interpret: bool = False) -> dict:
    """One streaming pass over x: (m, d), producing any requested subset of

      ``reduce``    (d,)   median/trimmed-mean/mean over the rows of
                           ``w @ x`` when ``w`` is given, of x otherwise;
      ``pairwise``  (m, m) squared L2 distances of the rows of x;
      ``combine``   (k, d) ``w @ x`` (requires ``w``: (k, m)).

    ``reduce`` ∈ {"med", "tm", "mean"}; ``trim`` (for "tm") is a Python int
    (statically sliced) or a traced int32 scalar (masked selection), both
    clipped to leave at least one surviving row. Returns a dict keyed by
    the requested stage names. d is padded up to a tile multiple with zero
    columns — inert for every stage (pairwise partials add 0; reduce and
    combine columns beyond d are sliced off).
    """
    if reduce is None and not pairwise and not combine:
        raise ValueError("fused_pass: request at least one of "
                         "reduce/pairwise/combine")
    if reduce is not None and reduce not in REDUCE_MODES:
        raise ValueError(f"unknown reduce mode {reduce!r}; want one of "
                         f"{REDUCE_MODES}")
    if combine and w is None:
        raise ValueError("fused_pass: the combine stage needs weights w")
    m, d = x.shape
    has_w = w is not None
    k = w.shape[0] if has_w else m  # rows entering the reduce stage
    traced_trim = (reduce == "tm"
                   and not isinstance(trim, (int, np.integer)))
    static_trim = 0
    if reduce == "tm" and not traced_trim:
        static_trim = min(int(trim), (k - 1) // 2)
    dp = -(-d // tile_d) * tile_d
    if dp != d:
        x = jnp.pad(x, ((0, 0), (0, dp - d)))

    in_specs, args = [], []
    if has_w:
        in_specs.append(pl.BlockSpec((k, m), lambda i: (0, 0)))
        args.append(w.astype(jnp.float32))
    in_specs.append(pl.BlockSpec((m, tile_d), lambda i: (0, i)))
    args.append(x)
    if traced_trim:
        in_specs.append(pl.BlockSpec((1,), lambda i: (0,)))
        args.append(jnp.clip(jnp.asarray(trim, jnp.int32),
                             0, (k - 1) // 2).reshape(1))
    out_specs, out_shapes, keys = [], [], []
    if reduce:
        out_specs.append(pl.BlockSpec((tile_d,), lambda i: (i,)))
        out_shapes.append(jax.ShapeDtypeStruct((dp,), jnp.float32))
        keys.append("reduce")
    if pairwise:
        out_specs.append(pl.BlockSpec((m, m), lambda i: (0, 0)))
        out_shapes.append(jax.ShapeDtypeStruct((m, m), jnp.float32))
        keys.append("pairwise")
    if combine:
        out_specs.append(pl.BlockSpec((k, tile_d), lambda i: (0, i)))
        out_shapes.append(jax.ShapeDtypeStruct((k, dp), jnp.float32))
        keys.append("combine")

    outs = pl.pallas_call(
        functools.partial(_fused_kernel, m=m, mode=reduce, trim=static_trim,
                          has_w=has_w, has_t=traced_trim, pairwise=pairwise,
                          combine=combine),
        grid=(dp // tile_d,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(*args)

    result = {}
    for key, val in zip(keys, outs):
        if key == "reduce":
            result[key] = val[:d]
        elif key == "pairwise":
            result[key] = jnp.maximum(val, 0.0)
        else:
            result[key] = val[:, :d]
    return result


# ------------------------------------------------- single-stage forms
#
# The public functions of the subsumed cwmed.py / pairwise.py / combine.py,
# each now one stage of the fused kernel (same kernel body, same numerics).


def cwmed(x: jax.Array, *, tile_d: int = 2048,
          interpret: bool = False) -> jax.Array:
    """Coordinate-wise median. x: (m, d) -> (d,) float32."""
    return fused_pass(x, reduce="med", tile_d=tile_d,
                      interpret=interpret)["reduce"]


def cwtm(x: jax.Array, trim: int, *, tile_d: int = 2048,
         interpret: bool = False) -> jax.Array:
    """Coordinate-wise trimmed mean. x: (m, d) -> (d,) float32."""
    return fused_pass(x, reduce="tm", trim=int(trim), tile_d=tile_d,
                      interpret=interpret)["reduce"]


def cwtm_masked(x: jax.Array, trim: jax.Array, *, tile_d: int = 2048,
                interpret: bool = False) -> jax.Array:
    """Trimmed mean with a traced trim scalar. x: (m, d) -> (d,) float32."""
    return fused_pass(x, reduce="tm", trim=jnp.asarray(trim, jnp.int32),
                      tile_d=tile_d, interpret=interpret)["reduce"]


def pairwise_sqdist(x: jax.Array, *, tile_d: int = 4096,
                    interpret: bool = False) -> jax.Array:
    """x: (m, d) -> (m, m) squared L2 distances, f32."""
    return fused_pass(x, pairwise=True, tile_d=tile_d,
                      interpret=interpret)["pairwise"]


def weighted_combine(x: jax.Array, w: jax.Array, *, tile_d: int = 2048,
                     interpret: bool = False) -> jax.Array:
    """x: (m, d), w: (k, m) -> (k, d) float32 (``w @ x`` streamed over d)."""
    return fused_pass(x, w=w, combine=True, tile_d=tile_d,
                      interpret=interpret)["combine"]


# ------------------------------------------------- cross distances
#
# GeoMed's Weiszfeld distances: the one primitive outside the fused pass
# (two row sets, and the numerics must avoid the gram expansion).


def _cross_kernel(x_ref, y_ref, o_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)  # (m, tile)
    y = y_ref[...].astype(jnp.float32)  # (k, tile)
    # direct subtraction, not the gram expansion: Weiszfeld iterates sit
    # close to the points and the expansion cancels catastrophically in f32
    # (see cross_sqdist_ref); k is tiny so the (m, k, tile) broadcast fits
    part = jnp.sum(jnp.square(x[:, None, :] - y[None, :, :]), axis=-1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = part

    @pl.when(i != 0)
    def _acc():
        o_ref[...] += part


def cross_sqdist(x: jax.Array, y: jax.Array, *, tile_d: int = 4096,
                 interpret: bool = False) -> jax.Array:
    """x: (m, d), y: (k, d) -> (m, k) squared L2 distances, f32.

    Same streaming reduction as the pairwise stage but between two row
    sets; the aggregation engine uses it for GeoMed's per-iteration
    distances to the Weiszfeld iterate (k = 1)."""
    m, d = x.shape
    k = y.shape[0]
    dp = -(-d // tile_d) * tile_d
    if dp != d:
        x = jnp.pad(x, ((0, 0), (0, dp - d)))
        y = jnp.pad(y, ((0, 0), (0, dp - d)))
    out = pl.pallas_call(
        _cross_kernel,
        grid=(dp // tile_d,),
        in_specs=[pl.BlockSpec((m, tile_d), lambda i: (0, i)),
                  pl.BlockSpec((k, tile_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((m, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        interpret=interpret,
    )(x, y)
    return jnp.maximum(out, 0.0)
