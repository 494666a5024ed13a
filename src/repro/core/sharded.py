"""Mode B — production sharded DynaBRO (multi-pod scale-out).

The paper's server aggregates m full gradients; at 398B–480B parameters that
is infeasible as stated. Key observation (DESIGN.md §3): the aggregation rules
used in the paper's experiments (CWMed / CWTM / Mean) are **coordinate-wise**,
so the aggregation itself can be sharded across every chip: replace the
data-parallel reduce-scatter with an **all-to-all** along the worker axes —
each device receives the m worker values for its own parameter shard and
aggregates locally. Same per-link communication volume as reduce-scatter.

``robust_all_gather`` packages this as a custom-VJP around the FSDP param
all-gather:

    forward : p_shard --all-gather(workers)--> p_full
    backward: per-worker cotangent gᵢ --[simulated Byzantine attack]
              --all-to-all(workers)--> (m, shard) --robust agg--> ĝ_shard

Because the hook is applied *inside* the layer-group scan, per-worker full
gradients only ever exist one layer-group at a time — this is what makes
Byzantine-robust training of the mega-architectures fit in HBM.

The per-shard aggregation itself dispatches through the shared engine
registry (``core.agg_engine``, DESIGN.md §4): the same rule objects Mode A
uses, with ref/pallas backends — so the Pallas kernels serve the Mode B
backward too.

Byzantine workers are *simulated*: the attack corrupts the cotangent of the
workers flagged by the (m,)-float mask (worker index = flattened position
along the worker axes, delivered as data — see ``make_param_hook``). IPM/ALIE
compute honest statistics with psum collectives — the exact omniscient
attacks of Appendix J.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.agg_engine import get_aggregator


@dataclasses.dataclass(frozen=True)
class ShardedByzConfig:
    axis_names: Tuple[str, ...]  # worker axes, e.g. ('data',) or ('pod','data')
    m: int  # product of worker axis sizes
    aggregator: str = "cwmed"  # coordinate-wise: mean | cwmed | cwtm
    delta: float = 0.25
    attack: str = "none"  # none | sign_flip | ipm | alie
    attack_param: float = 0.1
    backend: str = "auto"  # agg_engine backend: ref | pallas | auto


# ------------------------------------------------------------ aggregation


def _make_leaf_agg(cfg: ShardedByzConfig):
    """(m, ...) stack, model axis -> (...) robust aggregation via the shared
    engine registry.

    Mode B aggregates each parameter shard independently, which is exact only
    for coordinate-wise rules (DESIGN.md §3) — the engine's registry carries
    that metadata, so misconfiguration fails at build time, not in backward.

    The stack arrives inside the step's partial-manual region (manual over
    the worker axes, auto over 'model'). A compiled Pallas kernel cannot be
    partitioned automatically, so the rule runs under a nested ``shard_map``
    manual over every axis: each device aggregates its own 'model' slice of
    the coordinates (the leaf's ``model_axis``, -1 when the leaf is
    replicated over 'model'), exact for a coordinate-wise rule."""
    agg = get_aggregator(cfg.aggregator, delta=cfg.delta, backend=cfg.backend)
    if not agg.coordinate_wise:
        raise ValueError(
            f"sharded mode supports coordinate-wise rules, got {cfg.aggregator}")

    def leaf_agg(stack: jax.Array, model_axis: int) -> jax.Array:
        spec = [None] * stack.ndim
        if model_axis >= 0:
            spec[1 + model_axis] = "model"
        # manual over every axis, the worker axes again included: under
        # ``jax.set_mesh`` the nested region is lowered against the concrete
        # mesh, which does not know the outer region made them manual
        mesh = jax.sharding.get_abstract_mesh()
        return jax.shard_map(agg.leaf, mesh=mesh, in_specs=P(*spec),
                             out_specs=P(*spec[1:]),
                             axis_names=set(mesh.axis_names),
                             check_vma=False)(stack)

    return leaf_agg


def _attack_cotangent(g: jax.Array, maskf: jax.Array, idx: jax.Array,
                      cfg: ShardedByzConfig) -> jax.Array:
    """Corrupt this worker's cotangent if it is flagged Byzantine. ``idx`` is
    this device's flattened worker index (scalar int32, arrives as data)."""
    if cfg.attack == "none":
        return g
    byz = maskf[idx] > 0.5
    gf = g.astype(jnp.float32)
    n_honest = jnp.maximum(cfg.m - maskf.sum(), 1.0)
    if cfg.attack == "sign_flip":
        bad = -gf
    elif cfg.attack == "ipm":
        hsum = lax.psum(jnp.where(byz, 0.0, 1.0) * gf, cfg.axis_names)
        bad = -cfg.attack_param * hsum / n_honest
    elif cfg.attack == "alie":
        hg = jnp.where(byz, 0.0, 1.0) * gf
        mu = lax.psum(hg, cfg.axis_names) / n_honest
        var = lax.psum(jnp.where(byz, 0.0, 1.0) * jnp.square(gf - mu),
                       cfg.axis_names) / n_honest
        bad = mu - cfg.attack_param * jnp.sqrt(var + 1e-12)
    else:
        raise ValueError(cfg.attack)
    return jnp.where(byz, bad, gf).astype(g.dtype)


# ------------------------------------------------------------ collectives
#
# The worker index arrives as *data* (an iota sharded over the worker axes —
# see ``make_param_hook``), which the attack uses to read its own mask entry.


def _exchange_worker_blocks(g: jax.Array, cfg: ShardedByzConfig,
                            axis: int) -> jax.Array:
    """Worker all-to-all: full-size cotangent -> (m, ..., blk, ...) holding
    every worker's values for this device's own parameter shard."""
    ex = lax.all_to_all(g, cfg.axis_names, split_axis=axis, concat_axis=axis,
                        tiled=True)
    shp = ex.shape
    blk = shp[axis] // cfg.m
    ex = ex.reshape(shp[:axis] + (cfg.m, blk) + shp[axis + 1:])
    return jnp.moveaxis(ex, axis, 0)


# ------------------------------------------------- Mode A sharded substrate
#
# The compiled Mode A drivers (``core.robust_train.run_dynabro_scan``) reuse
# this module's substrate to lay the m simulated workers across devices: the
# per-worker gradient computation runs on each device's local worker slice,
# then the stacks are re-assembled with a worker-axis all_gather so the attack
# + aggregation code is *identical* to the single-device driver (DESIGN.md
# §7). Unlike the Mode B hooks above, the driver's shard_map region is
# *fully* manual (the mesh has only worker axes).


def gather_worker_stack(tree, axis_names):
    """(m_local, ...)-leaf tree -> (m, ...) in device order, inside a
    fully-manual shard_map region over ``axis_names``."""
    return jax.tree.map(
        lambda l: lax.all_gather(l, axis_names, axis=0, tiled=True), tree)


# ------------------------------------------------------------ custom VJPs


def make_robust_gather(cfg: ShardedByzConfig, gather_axis: int,
                       model_axis: int):
    """FSDP all-gather whose backward robust-aggregates instead of summing."""
    leaf_agg = _make_leaf_agg(cfg)

    @jax.custom_vjp
    def rg(p, maskf, widx):  # widx: f32 scalar worker index (see make_param_hook)
        return lax.all_gather(p, cfg.axis_names, axis=gather_axis, tiled=True)

    def fwd(p, maskf, widx):
        return rg(p, maskf, widx), (maskf, widx)

    def bwd(res, g):
        maskf, widx = res
        g = _attack_cotangent(g, maskf, widx.astype(jnp.int32), cfg)
        # exchange: every device ends up with the m worker values of its shard
        ex = _exchange_worker_blocks(g, cfg, gather_axis)
        return (leaf_agg(ex, model_axis).astype(g.dtype),
                jnp.zeros_like(maskf), jnp.zeros_like(widx))

    rg.defvjp(fwd, bwd)
    return rg


def make_robust_replicated(cfg: ShardedByzConfig, model_axis: int):
    """Identity on replicated params; backward gathers the m cotangents and
    robust-aggregates them (small leaves: norms, biases, routers)."""
    leaf_agg = _make_leaf_agg(cfg)

    @jax.custom_vjp
    def rr(p, maskf, widx):
        return p

    def fwd(p, maskf, widx):
        return rr(p, maskf, widx), (maskf, widx)

    def bwd(res, g):
        maskf, widx = res
        g = _attack_cotangent(g, maskf, widx.astype(jnp.int32), cfg)
        stack = lax.all_gather(g, cfg.axis_names, axis=0, tiled=False)
        return (leaf_agg(stack, model_axis).astype(g.dtype),
                jnp.zeros_like(maskf), jnp.zeros_like(widx))

    rr.defvjp(fwd, bwd)
    return rr


# ------------------------------------------------------------ param hook


def fsdp_axis_for(shape: Sequence[int], m: int, model_axis: Optional[int],
                  min_size: int = 1 << 16) -> Optional[int]:
    """Deterministic FSDP-axis rule shared by the spec builder and the hook:
    first axis (≠ model axis) divisible by the worker count, on leaves big
    enough to be worth sharding."""
    size = 1
    for s in shape:
        size *= s
    if size < min_size:
        return None
    for ax, s in enumerate(shape):
        if ax != model_axis and s % m == 0:
            return ax
    return None


def make_param_hook(cfg: ShardedByzConfig, plans: dict, maskf: jax.Array,
                    widx: jax.Array):
    """Tree hook with robust-aggregating backward.

    ``plans``: {scope: plan-tree}, plan trees structurally matching what the
    hook is called on (scope 'blocks' = one group slice; scope 'top' = the
    non-block params), each leaf an int FSDP axis (-1 => replicated), plus
    ``plans['model']``: {scope: plan-tree} of the leaves' 'model' axes (-1 =>
    none). Built once on global shapes by ``launch.sharding.plan_params``.

    ``widx``: this device's flattened worker index, delivered as data (the
    step builders feed an iota sharded over the worker axes — the local slice
    is the index). Any shape with one element; forwarded as an f32 scalar.
    """
    widx = jnp.asarray(widx, jnp.float32).reshape(())

    @functools.lru_cache(maxsize=None)
    def vjp_for(fa: int, ma: int):
        if fa < 0:
            return make_robust_replicated(cfg, ma)
        return make_robust_gather(cfg, fa, ma)

    def hook(tree, scope: str):
        return jax.tree.map(lambda p, fa, ma: vjp_for(fa, ma)(p, maskf, widx),
                            tree, plans[scope], plans["model"][scope])

    return hook


def tree_sq_norm(grads, plans_full: dict, axis_names) -> jax.Array:
    """Global ‖g‖² of a Mode-B gradient tree inside the manual region.

    FSDP-sharded leaves (plan >= 0) hold disjoint coordinate blocks per worker
    => psum their partial sums over the worker axes; replicated leaves (-1)
    are identical on every worker => no psum."""
    sq_sharded = jnp.zeros((), jnp.float32)
    sq_repl = jnp.zeros((), jnp.float32)
    flat_g, _ = jax.tree_util.tree_flatten(grads)
    flat_p, _ = jax.tree_util.tree_flatten(plans_full)
    for g, fa in zip(flat_g, flat_p):
        s = jnp.sum(jnp.square(g.astype(jnp.float32)))
        if fa >= 0:
            sq_sharded = sq_sharded + s
        else:
            sq_repl = sq_repl + s
    return lax.psum(sq_sharded, axis_names) + sq_repl


def make_global_norm(plans: dict, axis_names):
    """``norm_fn`` for ``mlmc.mlmc_combine`` inside the Mode-B manual region:
    the global ℓ2 norm of a worker-sharded gradient tree, assembled with one
    scalar psum over the worker axes (``tree_sq_norm``). ``plans`` is the
    plan tree from ``launch.sharding.plan_params`` ({'top': ..., 'blocks':
    ...}); the flattened full-tree plan is rebuilt here so every caller
    shares one layout convention."""
    plans_full = {k: v for k, v in plans["top"].items()}
    plans_full["blocks"] = plans["blocks"]

    def norm(diff):
        return jnp.sqrt(tree_sq_norm(diff, plans_full, axis_names))

    return norm
