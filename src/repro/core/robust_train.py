"""Mode A — paper-faithful DynaBRO training (Algorithms 1 & 2) + baselines.

Workers are simulated with ``vmap`` (exactly the paper's experimental setup):
per round t, each of the m workers computes ``2^{J_t}`` unit-batch gradients;
Byzantine workers (per the switching strategy, possibly changing *within* the
round) corrupt theirs; the server aggregates levels 0, J−1, J with a robust
rule, applies the MLMC combine + fail-safe filter, and takes an SGD /
AdaGrad-Norm step.

Baselines: worker-momentum (Karimireddy et al., 2021) and vanilla SGD —
robust aggregation of worker momentums / gradients.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Any, Callable, Dict, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import agg_engine
from repro.core import attacks as attacks_lib
from repro.core.aggregators import MFM, get_aggregator
from repro.core.mlmc import (
    MLMCConfig, level_prefix, level_schedule, mlmc_combine, round_cost,
)
from repro.core.switching import Switcher
from repro.optim.optimizers import Optimizer, apply_updates

GradFn = Callable[[Any, Any], Any]  # (params, unit_batch) -> grad tree


@dataclasses.dataclass
class DynaBROConfig:
    mlmc: MLMCConfig
    aggregator: str = "cwtm"  # any core.agg_engine registry rule
    delta: float = 0.25
    attack: str = "sign_flip"
    attack_kwargs: Optional[dict] = None
    use_mlmc: bool = True  # False -> plain robust-aggregated SGD
    agg_backend: str = "auto"  # engine backend: ref | pallas | auto
    # extra rule hyperparameters (Krum's multi, GeoMed's iters/eps, MFM's
    # tau, or a delta overriding the field above) — the per-cell mirror of
    # the sweep's per-lane agg theta (DESIGN.md §4)
    aggregator_kwargs: Optional[dict] = None


def _per_worker_grads(grad_fn: GradFn, params, batches):
    """batches: tree leading (m, n, ...) -> grads tree leading (m, n, ...)."""
    g1 = jax.vmap(grad_fn, in_axes=(None, 0))
    return jax.vmap(g1, in_axes=(None, 0))(params, batches)


def _attack_stack(cfg: DynaBROConfig, grads, masks, key, lane_attack=None):
    """grads: (m, n, ...) leaves; masks: (n, m) bool -> attacked grads.

    The per-computation key is ``fold_in(key, k)`` — a function of the
    within-round index k alone, so the k-th computation draws the same key
    whether the round runs at its exact batch size (legacy driver) or as the
    prefix of an n_max-padded batch (scan driver).

    ``lane_attack`` (an ``(apply, attack_id, theta)`` triple, with ``apply``
    from ``attacks.attack_switch``) routes through the traced per-lane attack
    dispatch of the lane-batched sweep instead of the cfg-static attack.
    """
    if lane_attack is None:
        atk0 = attacks_lib.get_attack(cfg.attack, **(cfg.attack_kwargs or {}))

        def atk(s, mk, k):
            return atk0(s, mk, key=k)
    else:
        apply_fn, attack_id, theta = lane_attack

        def atk(s, mk, k):
            return apply_fn(attack_id, s, mk, k, theta)
    swapped = jax.tree.map(lambda l: jnp.swapaxes(l, 0, 1), grads)  # (n, m, ...)
    keys = jax.vmap(lambda k: jax.random.fold_in(key, k))(
        jnp.arange(masks.shape[0]))
    attacked = jax.vmap(atk)(swapped, masks, keys)
    return jax.tree.map(lambda l: jnp.swapaxes(l, 0, 1), attacked)  # (m, n, ...)


def _aggregate(cfg: DynaBROConfig, stacked, n: int, lane_agg=None):
    """Robustly aggregate a worker-stacked tree; MFM threshold scales 1/√n.

    ``lane_agg`` (an ``(apply, agg_id, theta)`` triple, with ``apply`` from
    ``agg_engine.agg_switch``) routes through the traced per-lane rule
    dispatch of the lane-batched sweep instead of the cfg-static rule."""
    if lane_agg is not None:
        apply_fn, agg_id, theta = lane_agg
        return apply_fn(agg_id, stacked, n, theta)
    kw = dict(cfg.aggregator_kwargs or {})
    delta = kw.pop("delta", cfg.delta)
    if cfg.aggregator == "mfm":
        tau = kw.pop("tau", None)
        agg = MFM(backend=cfg.agg_backend, **kw)
        return agg.tree(stacked, tau=cfg.mlmc.mfm_tau(n) if tau is None else tau)
    agg = get_aggregator(cfg.aggregator, delta=delta, backend=cfg.agg_backend,
                         **kw)
    return agg.tree(stacked)


def _combine_from_levels(cfg: DynaBROConfig, g0_stack, gh, gbar_all, n: int,
                         j: int, lane_agg=None, lane_thr=None):
    """Aggregate the per-worker level means and apply the MLMC combine — the
    shared tail of ``_combine_levels`` (which feeds it slices of the stacked
    (m, n, ...) grads) and the microbatched scan branches (which feed it
    streamed accumulator means, DESIGN.md §9). g0_stack / gh / gbar_all are
    (m, ...) trees: each worker's level-0 unit, first-half mean and full
    mean; ``gh`` may be None whenever the MLMC branch below is dead.
    ``lane_thr`` is the per-lane fail-safe coefficient (1+√2)·c_E·C·V of the
    aggregator-lane sweep — c_E depends on the lane's rule (MFM is Option
    2), so it travels as data next to the lane's (agg_id, theta)."""
    if cfg.use_mlmc and j >= 1 and j <= cfg.mlmc.j_max:
        if lane_agg is not None:
            # all three levels through ONE rule dispatch: under vmap the
            # agg_switch select executes every branch per lane, so paying it
            # once per round instead of once per level is most of the
            # aggregator-lane sweep's win (DESIGN.md §7); the per-level
            # numerics are the exact scalar-n calls (agg_engine._per_level)
            apply_fn, agg_id, theta = lane_agg
            stacked = jax.tree.map(lambda a, b, c: jnp.stack([a, b, c]),
                                   g0_stack, gh, gbar_all)
            out = apply_fn(agg_id, stacked, (1, n // 2, n), theta)
            g0, gjm1, gj = (jax.tree.map(lambda l, i=i: l[i], out)
                            for i in range(3))
        else:
            g0 = _aggregate(cfg, g0_stack, 1)
            gjm1 = _aggregate(cfg, gh, n // 2)
            gj = _aggregate(cfg, gbar_all, n)
        thr = None if lane_thr is None else lane_thr / jnp.sqrt(2.0 ** j)
        return mlmc_combine(g0, gjm1, gj, j, cfg.mlmc, threshold=thr)
    g0 = _aggregate(cfg, g0_stack, 1, lane_agg)
    g, info = mlmc_combine(g0, None, None, cfg.mlmc.j_max + 1, cfg.mlmc)
    if not cfg.use_mlmc:  # plain robust SGD on the full mini-batch
        g = _aggregate(cfg, gbar_all, n, lane_agg)
    return g, info


def _combine_levels(cfg: DynaBROConfig, grads, j: int, lane_agg=None,
                    lane_thr=None):
    """Slice the attacked (m, n, ...) stack into the three level means and
    combine — the one round body shared by the per-level jitted step and
    every non-microbatched ``lax.switch`` branch of the scan driver, so the
    two cannot diverge. ``j`` and the leaf batch size n are static."""
    n = jax.tree.leaves(grads)[0].shape[1]
    gbar_all = jax.tree.map(lambda l: l.mean(1), grads)  # level j: mean of n
    g0_stack = jax.tree.map(lambda l: l[:, 0], grads)  # level 0: first sample
    gh = None
    if cfg.use_mlmc and j >= 1 and j <= cfg.mlmc.j_max:
        gh = jax.tree.map(lambda l: l[:, : n // 2].mean(1), grads)
    return _combine_from_levels(cfg, g0_stack, gh, gbar_all, n, j,
                                lane_agg=lane_agg, lane_thr=lane_thr)


def make_dynabro_step(grad_fn: GradFn, cfg: DynaBROConfig, opt: Optimizer):
    """Returns step(params, opt_state, batches, masks, key, j) jitted per level.

    batches: tree leading (m, 2^j) (or (m, 1) when j=0 / beyond cap);
    masks: (2^j, m) bool — within-round identity masks.
    """

    @functools.partial(jax.jit, static_argnames=("j",))
    def step(params, opt_state, batches, masks, key, j: int):
        grads = _per_worker_grads(grad_fn, params, batches)  # (m, n, ...)
        grads = _attack_stack(cfg, grads, masks, key)
        g, info = _combine_levels(cfg, grads, j)
        updates, opt_state = opt.update(g, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, info

    return step


def _make_momentum_round(grad_fn: GradFn, cfg: DynaBROConfig, lr: float,
                         beta: float, gather=None):
    """One worker-momentum round — shared by the jitted per-round step and
    the scan driver's body, so the two cannot diverge. ``gather`` re-assembles
    device-local worker slices into the full (m, ...) stack in the sharded
    driver (DESIGN.md §7); None on the single-device paths."""
    atk = attacks_lib.get_attack(cfg.attack, **(cfg.attack_kwargs or {}))

    def round_fn(params, worker_m, batches, mask, key):
        # batches: tree leading (m[_local],) unit batches; mask: (m,)
        grads = jax.vmap(grad_fn, in_axes=(None, 0))(params, batches)
        if gather is not None:
            grads = gather(grads)
        grads = atk(grads, mask, key=key)
        worker_m = jax.tree.map(
            lambda mm, gg: beta * mm + (1.0 - beta) * gg.astype(jnp.float32),
            worker_m, grads)
        agg = _aggregate(cfg, worker_m, 1)
        params = apply_updates(params, jax.tree.map(lambda x: lr * x, agg))
        return params, worker_m

    return round_fn


def make_momentum_step(grad_fn: GradFn, cfg: DynaBROConfig, lr: float, beta: float):
    """Worker-momentum baseline: attack on gradients feeding each worker's
    momentum recursion (App. E semantics); server robustly aggregates
    momentums. beta=0 recovers vanilla distributed SGD."""
    return jax.jit(_make_momentum_round(grad_fn, cfg, lr, beta))


# -------------------------------------------------------------- driver


@dataclasses.dataclass
class RoundLog:
    level: int
    failsafe_ok: bool
    n_byz: int
    cost: int


def run_dynabro(
    grad_fn: GradFn,
    params,
    opt: Optimizer,
    cfg: DynaBROConfig,
    switcher: Switcher,
    sample_batches: Callable[[int, int], Any],  # (t, n) -> tree leading (m, n)
    T: int,
    seed: int = 0,
    eval_fn: Optional[Callable[[Any, int], Dict[str, float]]] = None,
    eval_every: int = 0,
    step=None,
):
    """Run Algorithm 2 for T rounds. Returns (params, logs, evals).

    Reference Python-loop implementation — one compiled step dispatch per
    round; ``run_dynabro_scan`` is the compiled equivalent the parity suite
    checks against this. Pass a prebuilt ``step`` (from ``make_dynabro_step``)
    to reuse its jit cache across runs.

    Thin wrapper over ``repro.api.Session`` (DESIGN.md §10)."""
    from repro.api.session import Session
    sess = Session(cfg, grad_fn=grad_fn, params0=params, opt=opt,
                   switcher=switcher, sample_batches=sample_batches,
                   seed=seed)
    return sess.run(T, eval_fn=eval_fn, eval_every=eval_every,
                    driver="legacy", step=step)


def run_momentum(
    grad_fn: GradFn,
    params,
    cfg: DynaBROConfig,
    switcher: Switcher,
    sample_batches: Callable[[int, int], Any],
    T: int,
    lr: float,
    beta: float,
    seed: int = 0,
    eval_fn: Optional[Callable[[Any, int], Dict[str, float]]] = None,
    eval_every: int = 0,
    step=None,
):
    """Worker-momentum / vanilla-SGD baseline driver (same budget accounting
    is done by the caller: one unit batch per worker per round).

    Thin wrapper over ``repro.api.Session`` (DESIGN.md §10)."""
    from repro.api.session import Session
    sess = Session(cfg, grad_fn=grad_fn, params0=params, mode="momentum",
                   lr=lr, beta=beta, switcher=switcher,
                   sample_batches=sample_batches, seed=seed)
    return sess.run(T, eval_fn=eval_fn, eval_every=eval_every,
                    driver="legacy", step=step)


# ----------------------------------------------- compiled (lax.scan) drivers
#
# The Python-loop drivers above dispatch one compiled step per round and
# rebuild masks/batches on the host — O(T) dispatch overhead. The scan
# drivers precompute the full round schedule host-side (seeded identically,
# so they are round-for-round equivalent) and run the whole loop inside
# chunked ``lax.scan`` segments. DESIGN.md §5.


def _np_prng_keys(seeds) -> np.ndarray:
    """(T, 2) uint32 raw keys, entry i == ``jax.random.PRNGKey(seeds[i])``.

    Built with numpy (threefry seed layout: [s >> 32, s & 0xffffffff]) to
    avoid T per-round host->device dispatches; a probe key is checked against
    the runtime and on mismatch (non-default PRNG impl) we fall back to the
    per-seed PRNGKey loop.
    """
    seeds = np.asarray(seeds, np.int64)
    keys = np.stack([(seeds >> 32).astype(np.uint32),
                     (seeds & np.int64(0xFFFFFFFF)).astype(np.uint32)], -1)
    probe = np.asarray(jax.random.PRNGKey(int(seeds[0])))
    if probe.shape == keys[0].shape and (probe == keys[0]).all():
        return keys
    return np.stack([np.asarray(jax.random.PRNGKey(int(s))) for s in seeds])


def _pad_units(tree, n_max: int, axis: int):
    """Pad the within-round unit axis to n_max by repeating the first unit
    (branch j only ever reads the first 2^j units, so pad values are inert)."""
    def pad(l):
        n = l.shape[axis]
        if n == n_max:
            return l
        idx = [slice(None)] * l.ndim
        idx[axis] = slice(0, 1)
        reps = list(l.shape)
        reps[axis] = n_max - n
        return jnp.concatenate(
            [l, jnp.broadcast_to(l[tuple(idx)], tuple(reps))], axis=axis)
    return jax.tree.map(pad, tree)


def _batch_schedule(sample_batches, tn, n_max: int, vectorize: bool = True):
    """Stack per-round batches into an (L, m, n_max, ...) padded schedule.

    ``tn`` is the segment's [(t, n_t), ...]; each round calls
    ``sample_batches(t, n_t)`` at the exact per-round batch size of the legacy
    driver (the sampler's output may depend on n, so padding must happen
    *after* sampling to preserve parity). Rounds are grouped by level and the
    sampler is vmapped over t, so host-side cost is O(#levels) dispatches
    instead of O(T); a probe round is compared against the direct call and any
    sampler that is not traceable in t — or ignores a traced t — falls back to
    the per-round loop.

    The vectorized path requires the sampler to be a pure function of (t, n):
    the vmap trace and the probe each invoke it extra times, which would
    advance any hidden per-call state before the fallback replays the rounds.
    Such samplers must run with ``vectorize=False`` — the per-round loop
    calls the sampler exactly once per round, in round order, like the legacy
    driver.
    """
    if vectorize:
        try:
            groups: Dict[int, list] = {}
            for i, (t, n) in enumerate(tn):
                groups.setdefault(int(n), []).append((i, int(t)))
            out = None
            for n, its in sorted(groups.items()):
                idx = jnp.asarray(np.array([i for i, _ in its], np.int32))
                ts = jnp.asarray(np.array([t for _, t in its], np.int32))
                bt = jax.vmap(lambda t: sample_batches(t, n))(ts)
                bt = _pad_units(bt, n_max, axis=2)
                if out is None:
                    out = jax.tree.map(
                        lambda l: jnp.zeros((len(tn),) + l.shape[1:], l.dtype),
                        bt)
                out = jax.tree.map(lambda o, l: o.at[idx].set(l), out, bt)
            n_probe, its_probe = max(groups.items(), key=lambda kv: len(kv[1]))
            i0, t0 = its_probe[-1]
            want = _pad_units(sample_batches(t0, n_probe), n_max, axis=1)
            got = jax.tree.map(lambda l: l[i0], out)
            if not all(bool(jnp.array_equal(a, b)) for a, b in
                       zip(jax.tree.leaves(got), jax.tree.leaves(want))):
                raise ValueError("vectorized sampler disagrees with direct call")
            return out
        except (TypeError, ValueError) as e:
            # TypeError: sampler not traceable in t (jax tracer-leak errors
            # subclass it); ValueError: probe mismatch / host-side shape
            # complaints. Anything else (OOM, internal bugs) propagates —
            # silently reverting to O(T) dispatch would mask it.
            warnings.warn(
                f"run_*_scan: per-round batch sampling fallback ({e}); pass "
                "vectorize_batches=False to silence", RuntimeWarning)
    rows = [_pad_units(sample_batches(t, int(n)), n_max, axis=1)
            for t, n in tn]
    return jax.tree.map(lambda *ls: jnp.stack(ls), *rows)


def _level_plan(cfg: DynaBROConfig, rng: np.random.Generator, T: int):
    """Host-side MLMC level plan: (levels (T,), per-round unit counts ns,
    n_max) — replaying the exact level stream the legacy driver draws.
    Shared by ``run_dynabro_scan`` and the vmapped sweep, which must agree
    round for round."""
    j_max = cfg.mlmc.j_max
    if cfg.use_mlmc:
        levels = level_schedule(rng, j_max, T)
        n_max = 2 ** j_max
        ns = np.where(levels <= j_max, 2 ** levels.astype(np.int64), 1)
    else:
        levels = np.zeros(T, np.int32)
        n_max = 1
        ns = np.ones(T, np.int64)
    return levels, ns, n_max


def _round_logs(levels, ok, masks, j_max: int) -> list:
    """Per-round RoundLog list from the level plan, the scanned fail-safe
    flags (T,) and the (T, n_max, m) mask schedule — the compiled drivers'
    side of the ``mlmc.round_cost`` cost-accounting contract (beyond-cap
    rounds, j > j_max, cost 1: the correction is dropped)."""
    logs = []
    for t in range(len(levels)):
        j = int(levels[t])
        logs.append(RoundLog(j, bool(ok[t]), int(masks[t, 0].sum()),
                             round_cost(j, j_max)))
    return logs


def _mask_schedule(switcher: Switcher, T: int, n_max: int,
                   ns: np.ndarray) -> np.ndarray:
    """(T, n_max, m) identity schedule for one switcher — the vectorized
    ``mask_schedule`` fast path when ``within_round`` is the stock one, else a
    replay of the legacy driver's exact call sequence (only the n_t
    computations of each round; pad rows are never read by the level
    branches, so stateful within-round strategies stay exact)."""
    if type(switcher).within_round is Switcher.within_round:
        return switcher.mask_schedule(T, n_max)
    masks = np.zeros((T, n_max, switcher.m), bool)
    for t in range(T):
        for k in range(int(ns[t])):
            masks[t, k] = switcher.within_round(t, k)
    return masks


def _check_scan_fn_mesh(scan_fn, mesh) -> None:
    """Reject a prebuilt scan_fn whose build-time mesh disagrees with this
    run's ``mesh=``: an unsharded fn passed with a mesh would silently run
    the whole loop unsharded (and vice versa). Fns built outside
    ``make_*_scan_fn`` carry no tag and are trusted."""
    have = getattr(scan_fn, "worker_mesh", mesh)
    if (have is None) != (mesh is None) or have != mesh:
        raise ValueError(
            f"scan_fn was built with mesh={have}, but this run passes "
            f"mesh={mesh}; rebuild the scan_fn with the same mesh")


def _check_worker_mesh(mesh, worker_axis: str, m: int,
                       allow_model: bool = True) -> None:
    axes = tuple(mesh.axis_names)
    allowed = ((worker_axis,), (worker_axis, "model")) if allow_model \
        else ((worker_axis,),)
    if axes not in allowed:
        want = f"1-axis ({worker_axis!r},)" + (
            f" or 2-axis ({worker_axis!r}, 'model')" if allow_model else "")
        raise ValueError(
            f"sharded driver needs a {want} mesh, got "
            f"axes {axes} (see launch.mesh.make_worker_mesh)")
    n_dev = mesh.shape[worker_axis]
    if m % n_dev:
        raise ValueError(
            f"worker count m={m} not divisible by the {worker_axis!r} mesh "
            f"axis size {n_dev}")


def _check_lane_mesh(mesh, lane_axis: str, worker_axis: str,
                     m: Optional[int] = None) -> None:
    """Reject a sweep mesh that is not the 2-axis ``(lanes, workers)`` form
    (DESIGN.md §12); with ``m`` also checks worker divisibility (the lane
    divisibility check needs the lane count and lives in the sweep)."""
    axes = tuple(mesh.axis_names)
    if axes != (lane_axis, worker_axis):
        raise ValueError(
            f"sharded sweeps need a 2-axis ({lane_axis!r}, {worker_axis!r}) "
            f"mesh, got axes {axes} (see launch.mesh.make_lane_mesh)")
    if m is not None and m % mesh.shape[worker_axis]:
        raise ValueError(
            f"worker count m={m} not divisible by the {worker_axis!r} mesh "
            f"axis size {mesh.shape[worker_axis]}")


def _norm_mesh(mesh):
    """The sweep's 1-device bitwise contract (DESIGN.md §12): a mesh whose
    device count is 1 is the unsharded path — normalize it to ``None`` so
    the shard_map wrap is skipped entirely."""
    if mesh is None:
        return None
    if math.prod(list(mesh.shape.values())) == 1:
        return None
    return mesh


def _segment_bounds(T: int, eval_every: int, chunk: int):
    stops = {T}
    if eval_every:
        stops |= set(range(eval_every, T + 1, eval_every))
    if chunk and chunk > 0:
        stops |= set(range(chunk, T + 1, chunk))
    return sorted(stops)


def make_dynabro_scan_fn(grad_fn: GradFn, cfg: DynaBROConfig, opt: Optimizer,
                         *, mesh=None, worker_axis: str = "workers",
                         lane_attacks: Optional[Sequence[str]] = None,
                         lane_aggregators: Optional[Sequence[str]] = None,
                         param_specs=None, microbatch: bool = False,
                         sweep_mesh=None, lane_axis: str = "lanes"):
    """Build the compiled DynaBRO round loop (DESIGN.md §5, §7).

    Returns a jitted ``seg((params, opt_state), xs)`` running ``lax.scan``
    over a round schedule ``xs = (level, batches, masks, keys)`` (leading time
    axis; batches padded to n_max units, masks (n_max, m) per round). The scan
    body dispatches the host-sampled MLMC level via ``lax.switch`` whose
    branch j slices the level's nested batch prefix, applies the attack
    in-graph, robust-aggregates levels 0/J-1/J and applies the fail-safe
    combine — numerically identical to ``make_dynabro_step`` at that level.
    Reusable across ``run_dynabro_scan`` calls (jit caches per segment
    length); emits stacked (failsafe_ok, corr_norm) per round.

    With ``mesh`` (a 1-axis device mesh from ``launch.mesh.make_worker_mesh``)
    the whole segment compiles under a fully-manual ``shard_map``: the batch
    schedule is split over ``worker_axis`` so each device runs the per-worker
    gradient ``vmap`` on its local worker slice only, the stacks are
    re-assembled with a worker-axis all_gather, and the attack + aggregation
    + update code is byte-for-byte the single-device body — which is why a
    1-device mesh is bitwise-identical to ``mesh=None`` (DESIGN.md §7).

    ``lane_attacks`` (a sequence of attack names) builds the lane-batched
    sweep variant instead: the segment takes a third argument
    ``atk = (attack_id, theta)`` — a scalar index into ``lane_attacks`` plus
    the (N_PARAMS,) parameter vector, both loop-invariant — and the scan body
    dispatches the attack via a second ``lax.switch``
    (``attacks.attack_switch``). ``lane_aggregators`` does the same for the
    aggregation rule: the segment takes a fourth argument
    ``agg = (agg_id, theta, thr_coeff)`` — an index into ``lane_aggregators``,
    the (N_AGG_PARAMS,) hyperparameter vector and the lane's fail-safe
    coefficient (1+√2)·c_E·C·V — dispatched via ``agg_engine.agg_switch`` at
    every aggregation site. Either axis may be present alone (the segment
    signature is always ``seg(carry, xs, atk, agg)`` with ``None`` for the
    absent one). The MLMC level switch is untouched (its index stays scalar
    and shared across lanes). Both are mutually exclusive with ``mesh`` —
    sweeps run unsharded (DESIGN.md §7).

    A **2-axis** ``(workers, 'model')`` mesh selects the model-zoo GSPMD path
    instead (DESIGN.md §9): no shard_map — the segment jits as-is and
    ``with_sharding_constraint`` pins params / batches / the per-worker grad
    stacks, letting GSPMD compose the worker axis with FSDP+model parameter
    sharding (``param_specs``, a PartitionSpec tree over the param structure
    from ``launch.sharding.plan_params``; None = replicated params, worker
    sharding on the stacks only). On a mesh whose axes are all size 1 the
    constraints are skipped entirely, so the traced graph — and hence the
    result — is bitwise-identical to ``mesh=None`` by construction, exactly
    like the 1-axis path's skipped gather.

    ``microbatch`` streams each level-j round's 2^j units through a
    ``lax.scan`` grad-accumulation loop instead of materializing the
    (m, 2^j, ...) per-worker gradient stack: per unit k the (m, ...) worker
    grads are computed, attacked (same ``fold_in(key, k)`` keying) and summed
    into three f32 accumulators (level-0 snapshot, first-half sum, full sum)
    whose means feed the identical combine tail (``_combine_from_levels``).
    Summation order differs from the stacked path, so microbatched runs are
    *not* bitwise against non-microbatched ones — the parity contract is
    microbatched-sharded == microbatched-unsharded. Incompatible with the
    lane axes (sweeps materialize by design).

    ``sweep_mesh`` (a 2-axis ``(lanes, workers)`` mesh from
    ``launch.mesh.make_lane_mesh``) builds the sweep variants for the
    *sharded* vmapped sweep (DESIGN.md §12): the returned segment is
    un-jitted (the sweep wraps it in ``shard_map`` around the vmapped
    wrapper) and its per-worker gradient stack is re-assembled with a
    ``worker_axis`` all_gather exactly as on the 1-axis mesh path — skipped
    when the mesh's worker axis has one device, so a 1-device lane mesh
    stays bitwise-identical to the unsharded sweep by construction.
    Exclusive with ``mesh=`` and ``microbatch``.
    """
    if (lane_attacks is not None or lane_aggregators is not None) \
            and mesh is not None:
        raise ValueError(
            "lane_attacks/lane_aggregators are for the vmapped sweep, which "
            "runs unsharded; drop mesh= (DESIGN.md §7)")
    if sweep_mesh is not None:
        if mesh is not None:
            raise ValueError(
                "sweep_mesh= (the vmapped sweep's lane mesh) and mesh= (the "
                "per-run worker mesh) are exclusive; see DESIGN.md §12")
        if microbatch:
            raise ValueError(
                "microbatch streaming is not supported on the sweep "
                "variants (DESIGN.md §9); drop sweep_mesh/microbatch")
        _check_lane_mesh(sweep_mesh, lane_axis, worker_axis)
    if microbatch and (lane_attacks is not None
                       or lane_aggregators is not None):
        raise ValueError(
            "microbatch streaming is not supported on the lane-batched sweep "
            "variant (DESIGN.md §9); drop lane_attacks/lane_aggregators")
    gspmd = mesh is not None and "model" in mesh.axis_names
    if param_specs is not None and not gspmd:
        raise ValueError(
            "param_specs only applies to the 2-axis (workers, 'model') GSPMD "
            "path; the 1-axis shard_map path replicates params (DESIGN.md §9)")
    j_max = cfg.mlmc.j_max
    n_max = 2 ** j_max if cfg.use_mlmc else 1
    gather = None if gspmd else _worker_gather(
        mesh if mesh is not None else sweep_mesh, worker_axis)
    constrain = _gspmd_constraints(mesh, worker_axis, param_specs) \
        if gspmd else None
    if (constrain is not None and cfg.agg_backend == "auto"
            and mesh.devices.flat[0].platform == "tpu"):
        # GSPMD partitions this path's aggregation itself, and a compiled
        # (Mosaic) kernel cannot be partitioned automatically: no manual
        # region hosts it here, so the rules run on the XLA reference
        cfg = dataclasses.replace(cfg, agg_backend="ref")
    atk_one = (attacks_lib.get_attack(cfg.attack, **(cfg.attack_kwargs or {}))
               if microbatch else None)
    atk_apply = (attacks_lib.attack_switch(tuple(lane_attacks))
                 if lane_attacks is not None else None)
    agg_apply = (agg_engine.agg_switch(tuple(lane_aggregators),
                                       backend=cfg.agg_backend, mlmc=cfg.mlmc)
                 if lane_aggregators is not None else None)

    def _stream_levels(b, params, masks, key, n: int, j: int):
        """Microbatched round body (DESIGN.md §9): stream the n units through
        a grad-accumulation scan instead of materializing the (m, n, ...)
        stack. Three f32 accumulators — the level-0 snapshot (unit k=0), the
        first-half sum and the full sum — replace the three prefix slices of
        ``_combine_levels``; their means (cast back to the grad dtype, so the
        scan carry dtype is stable) feed the identical combine tail."""
        m = masks.shape[1]
        mlmc_live = cfg.use_mlmc and 1 <= j <= j_max
        bs = jax.tree.map(lambda l: jnp.swapaxes(l, 0, 1), b)  # (n, m[_l], ..)
        zeros = jax.tree.map(
            lambda p: jnp.zeros((m,) + p.shape, jnp.float32), params)
        if constrain is not None:
            zeros = constrain.stack(zeros, lead=1)

        def unit(accs, x):
            bk, mk, k = x
            g = jax.vmap(grad_fn, in_axes=(None, 0))(params, bk)  # (m[_l], ..)
            if gather is not None:
                g = gather(g)
            if constrain is not None:
                g = constrain.stack(g, lead=1)
            g = atk_one(g, mk, key=jax.random.fold_in(key, k))
            g32 = jax.tree.map(lambda l: l.astype(jnp.float32), g)
            a0, ah, aa = accs
            a0 = jax.tree.map(lambda a, v: jnp.where(k == 0, v, a), a0, g32)
            if ah is not None:
                ah = jax.tree.map(
                    lambda a, v: jnp.where(k < n // 2, a + v, a), ah, g32)
            aa = jax.tree.map(lambda a, v: a + v, aa, g32)
            return (a0, ah, aa), ()

        accs0 = (zeros, zeros if mlmc_live else None, zeros)
        (a0, ah, aa), _ = jax.lax.scan(
            unit, accs0, (bs, masks[:n], jnp.arange(n)))

        def mean(t, c):
            return jax.tree.map(lambda l, p: (l / c).astype(p.dtype),
                                t, params)

        g0_stack = jax.tree.map(lambda l, p: l.astype(p.dtype), a0, params)
        gh = mean(ah, n // 2) if mlmc_live else None
        return _combine_from_levels(cfg, g0_stack, gh, mean(aa, n), n, j)

    def level_branch(j: int):
        n = 2 ** j if (cfg.use_mlmc and 1 <= j <= j_max) else 1

        def branch(operand):
            params, batches, masks, key, atk, agg = operand
            lane = None if atk_apply is None else (atk_apply, *atk)
            lane_agg = None if agg_apply is None else (agg_apply, *agg[:2])
            lane_thr = None if agg_apply is None else agg[2]
            b = level_prefix(batches, n, n_max, axis=1)
            if constrain is not None:
                b = constrain.batch(b)
            if microbatch:
                g, info = _stream_levels(b, params, masks, key, n, j)
                return g, info["failsafe_ok"], info["corr_norm"]
            grads = _per_worker_grads(grad_fn, params, b)  # (m[_local], n, ...)
            if gather is not None:
                grads = gather(grads)  # (m, n, ...) in worker order
            if constrain is not None:
                grads = constrain.stack(grads, lead=2)
            grads = _attack_stack(cfg, grads, masks[:n], key, lane_attack=lane)
            g, info = _combine_levels(cfg, grads, j, lane_agg=lane_agg,
                                      lane_thr=lane_thr)
            return g, info["failsafe_ok"], info["corr_norm"]

        return branch

    branches = ([level_branch(j) for j in range(1, j_max + 2)]
                if cfg.use_mlmc else [level_branch(0)])

    def body(carry, xs, atk=None, agg=None):
        params, opt_state = carry
        if constrain is not None:
            params = constrain.params(params)
        level, batches, masks, key = xs
        operand = (params, batches, masks, key, atk, agg)
        if cfg.use_mlmc:
            g, ok, dn = jax.lax.switch(level - 1, branches, operand)
        else:
            g, ok, dn = branches[0](operand)
        updates, opt_state = opt.update(g, opt_state, params)
        params = apply_updates(params, updates)
        return (params, opt_state), (ok, dn)

    if lane_attacks is not None or lane_aggregators is not None:
        def seg_lane(carry, xs, atk=None, agg=None):
            return jax.lax.scan(lambda c, x: body(c, x, atk, agg), carry, xs)

        # un-jitted: the sweep jits the vmapped wrapper anyway, and a plain
        # function can carry the branch orders for the sweep's id-consistency
        # checks (a mismatched order would silently apply the wrong attack
        # or rule per lane)
        seg_lane.lane_attacks = (tuple(lane_attacks)
                                 if lane_attacks is not None else None)
        seg_lane.lane_aggregators = (tuple(lane_aggregators)
                                     if lane_aggregators is not None else None)
        seg_lane.sweep_mesh = sweep_mesh
        return seg_lane

    def seg(carry, xs):
        return jax.lax.scan(body, carry, xs)

    if sweep_mesh is not None:
        # the no-lane-axis sweep form: un-jitted like seg_lane (the sweep
        # jits the shard_map-wrapped vmapped wrapper), tagged so the sweep
        # can reject a mesh mismatch
        seg.lane_attacks = None
        seg.lane_aggregators = None
        seg.sweep_mesh = sweep_mesh
        return seg

    if mesh is None or gspmd:
        # GSPMD path: no shard_map — the in-graph with_sharding_constraint
        # pins (or, on an all-size-1 mesh, their absence) are the whole story
        jitted = jax.jit(seg)
    else:
        jitted = jax.jit(_shard_seg(
            seg, mesh, worker_axis,
            xs_batch_axes=(None, worker_axis, None, None)))
    # tag the build mode so the drivers can reject a mismatched prebuilt fn
    # (an unsharded scan_fn passed with mesh= would silently run unsharded)
    jitted.worker_mesh = mesh
    jitted.microbatch = microbatch
    return jitted


def _worker_gather(mesh, worker_axis: str):
    """The stack re-assembly hook of the sharded scan body, or None when
    there is nothing to re-assemble (no mesh, or a 1-device mesh whose local
    slice already IS the full stack). Skipping the no-op gather on the
    1-device mesh keeps the parity contract bitwise *by construction* — even
    an identity all_gather inserts a copy that can change how XLA fuses (and
    FMA-contracts) the surrounding ops."""
    if mesh is None or mesh.shape[worker_axis] == 1:
        return None
    from repro.core.sharded import gather_worker_stack

    def gather(tree):
        return gather_worker_stack(tree, worker_axis)

    return gather


def _shard_seg(seg, mesh, worker_axis: str, xs_batch_axes):
    """Wrap a segment fn in a fully-manual ``shard_map`` over ``worker_axis``.

    Params / optimizer state / worker momenta are replicated (every device
    applies the identical update to the identical aggregate — deterministic,
    so the replication claim holds by construction); of the xs schedule only
    the batch tree is split, on its worker axis (leaf axis 1, after the time
    axis). Masks / keys / levels are replicated: the attack consumes the full
    (n, m) mask once the worker stacks are gathered.
    """
    from jax.sharding import PartitionSpec as P

    xs_specs = tuple(P(None) if a is None else P(None, a) for a in xs_batch_axes)
    return jax.shard_map(
        seg, mesh=mesh,
        in_specs=(P(), xs_specs),
        out_specs=(P(), P(None)),
        axis_names={worker_axis}, check_vma=False)


class _GspmdConstraints:
    """``with_sharding_constraint`` pins for the 2-axis GSPMD zoo path
    (DESIGN.md §9). Unlike the 1-axis path's manual shard_map, nothing here
    rewrites the computation — the segment jits as-is and these pins only
    tell GSPMD where the parallelism lives: params per their per-leaf
    ``launch.sharding.plan_params`` specs, batches and per-worker grad
    stacks split over the worker axis. Everything else (optimizer state,
    aggregates, the update) is left to GSPMD propagation."""

    def __init__(self, mesh, worker_axis: str, param_specs):
        self.mesh = mesh
        self.worker_axis = worker_axis
        self.param_specs = param_specs

    def _pin(self, leaf, spec):
        from jax.sharding import NamedSharding
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(self.mesh, spec))

    def _specs_for(self, tree):
        """param_specs leaves aligned to ``tree``'s leaves (PartitionSpec is
        a registered pytree *leaf*, so flatten_up_to stops at each spec)."""
        return jax.tree.structure(tree).flatten_up_to(self.param_specs)

    def params(self, tree):
        """Pin params to their full FSDP/model specs; no-op when replicated."""
        if self.param_specs is None:
            return tree
        td = jax.tree.structure(tree)
        return jax.tree.unflatten(
            td, [self._pin(l, s)
                 for l, s in zip(jax.tree.leaves(tree), self._specs_for(tree))])

    def stack(self, tree, lead: int):
        """Pin a worker-stacked tree — leading (m,) (lead=1) or (m, n)
        (lead=2) axes, m split over the worker axis. Of the param dims only
        'model' entries survive: the FSDP entry IS the worker axis, already
        spent on the leading m dim, and a mesh axis cannot appear twice in
        one PartitionSpec."""
        from jax.sharding import PartitionSpec as P
        if self.param_specs is None:
            spec = P(self.worker_axis)
            return jax.tree.map(lambda l: self._pin(l, spec), tree)
        td = jax.tree.structure(tree)
        out = []
        for l, s in zip(jax.tree.leaves(tree), self._specs_for(tree)):
            tail = tuple(e if e == "model" else None for e in tuple(s))
            out.append(self._pin(
                l, P(self.worker_axis, *((None,) * (lead - 1)), *tail)))
        return jax.tree.unflatten(td, out)

    def batch(self, tree):
        """Pin per-round batches: the leading (m,) worker dim split."""
        from jax.sharding import PartitionSpec as P
        spec = P(self.worker_axis)
        return jax.tree.map(lambda l: self._pin(l, spec), tree)

    def put_params(self, tree):
        """Host-side companion to ``params``: place the initial params per
        their specs before the first segment call, so entry into the jitted
        segment starts from the sharded layout instead of committing a fully
        replicated copy first."""
        from jax.sharding import NamedSharding
        if self.param_specs is None:
            return tree
        td = jax.tree.structure(tree)
        return jax.tree.unflatten(
            td, [jax.device_put(l, NamedSharding(self.mesh, s))
                 for l, s in zip(jax.tree.leaves(tree), self._specs_for(tree))])


def _gspmd_constraints(mesh, worker_axis: str, param_specs):
    """The GSPMD pin hook, or None on an all-size-1 mesh: with every
    constraint skipped the traced graph is *identical* to ``mesh=None``,
    which is what makes the (1, 1)-mesh parity contract bitwise by
    construction (DESIGN.md §9) — the GSPMD analog of ``_worker_gather``
    returning None for a 1-device mesh."""
    if math.prod(list(mesh.shape.values())) == 1:
        return None
    return _GspmdConstraints(mesh, worker_axis, param_specs)


def run_dynabro_scan(
    grad_fn: GradFn,
    params,
    opt: Optimizer,
    cfg: DynaBROConfig,
    switcher: Switcher,
    sample_batches: Callable[[int, int], Any],
    T: int,
    seed: int = 0,
    eval_fn: Optional[Callable[[Any, int], Dict[str, float]]] = None,
    eval_every: int = 0,
    chunk: int = 0,
    scan_fn=None,
    vectorize_batches: bool = True,
    mesh=None,
    worker_axis: str = "workers",
    param_specs=None,
    microbatch: bool = False,
):
    """Compiled drop-in for ``run_dynabro``: same signature, same returns,
    round-for-round equivalent schedules (level RNG stream, switching masks,
    per-round PRNG keys, per-round batch draws).

    ``chunk`` bounds how many rounds of padded batches are resident at once
    (0 = whole segments between eval points); ``scan_fn`` accepts a prebuilt
    ``make_dynabro_scan_fn`` result for cross-run jit reuse. Pass
    ``vectorize_batches=False`` for samplers with hidden per-call state —
    the sampler is then called exactly once per round, in round order, like
    the legacy driver (see ``_batch_schedule``).

    ``mesh`` (a 1-axis worker mesh, ``launch.mesh.make_worker_mesh``) runs the
    loop sharded: per-worker gradients computed on each device's worker slice,
    the rest of the round body replicated after a worker all_gather — bitwise
    identical on a 1-device mesh, and the schedule precompute is unchanged
    (DESIGN.md §7). Requires ``switcher.m`` divisible by the mesh axis size.

    A 2-axis ``(workers, 'model')`` mesh takes the model-zoo GSPMD path
    instead, with ``param_specs`` (the PartitionSpec tree from
    ``launch.sharding.plan_params``) sharding the parameters FSDP-style over
    the worker axis and tensor-style over 'model'; ``microbatch`` streams
    each round's MLMC units through a grad-accumulation scan so no full
    (m, 2^j, ...) gradient stack is ever materialized (DESIGN.md §9). Both
    forward to ``make_dynabro_scan_fn`` — see its docstring for the parity
    contracts.

    Thin wrapper over ``repro.api.Session`` (DESIGN.md §10) — the Session
    carries the identical preflight validation and segment loop.
    """
    from repro.api.session import Session
    sess = Session(cfg, grad_fn=grad_fn, params0=params, opt=opt,
                   switcher=switcher, sample_batches=sample_batches,
                   seed=seed, scan_fn=scan_fn,
                   vectorize_batches=vectorize_batches, mesh=mesh,
                   worker_axis=worker_axis, param_specs=param_specs,
                   microbatch=microbatch)
    return sess.run(T, eval_fn=eval_fn, eval_every=eval_every, chunk=chunk)


def make_momentum_scan_fn(grad_fn: GradFn, cfg: DynaBROConfig, lr: float,
                          beta: float, *, mesh=None,
                          worker_axis: str = "workers"):
    """Compiled worker-momentum baseline loop: the shared round body of
    ``make_momentum_step``, scanned over (batches, masks, keys) schedules.
    ``mesh`` (1-axis only — the 2-axis GSPMD zoo path is DynaBRO-only,
    DESIGN.md §9) shards the per-worker gradient vmap across devices exactly
    as in ``make_dynabro_scan_fn`` (worker momenta stay replicated)."""
    if mesh is not None and "model" in mesh.axis_names:
        raise ValueError(
            "momentum scan driver supports only 1-axis worker meshes; the "
            "2-axis (workers, 'model') GSPMD path is DynaBRO-only "
            "(DESIGN.md §9)")
    round_fn = _make_momentum_round(grad_fn, cfg, lr, beta,
                                    gather=_worker_gather(mesh, worker_axis))

    def body(carry, xs):
        batch, mask, key = xs
        return round_fn(carry[0], carry[1], batch, mask, key), ()

    def seg(carry, xs):
        return jax.lax.scan(body, carry, xs)

    if mesh is None:
        jitted = jax.jit(seg)
    else:
        jitted = jax.jit(_shard_seg(seg, mesh, worker_axis,
                                    xs_batch_axes=(worker_axis, None, None)))
    jitted.worker_mesh = mesh
    return jitted


def run_momentum_scan(
    grad_fn: GradFn,
    params,
    cfg: DynaBROConfig,
    switcher: Switcher,
    sample_batches: Callable[[int, int], Any],
    T: int,
    lr: float,
    beta: float,
    seed: int = 0,
    eval_fn: Optional[Callable[[Any, int], Dict[str, float]]] = None,
    eval_every: int = 0,
    chunk: int = 0,
    scan_fn=None,
    vectorize_batches: bool = True,
    mesh=None,
    worker_axis: str = "workers",
):
    """Compiled drop-in for ``run_momentum`` (same signature + chunking).
    ``mesh`` runs it sharded over the worker axis (1-axis meshes only,
    DESIGN.md §7).

    Thin wrapper over ``repro.api.Session`` (DESIGN.md §10)."""
    from repro.api.session import Session
    sess = Session(cfg, grad_fn=grad_fn, params0=params, mode="momentum",
                   lr=lr, beta=beta, switcher=switcher,
                   sample_batches=sample_batches, seed=seed, scan_fn=scan_fn,
                   vectorize_batches=vectorize_batches, mesh=mesh,
                   worker_axis=worker_axis)
    return sess.run(T, eval_fn=eval_fn, eval_every=eval_every, chunk=chunk)


# ----------------------------------------------- vmapped scenario sweeps
#
# Whole attack × switcher × aggregator grids re-run the compiled driver per
# cell; cells that differ only in their *switching strategy, attack and
# attack kwargs* share every other schedule (the level RNG stream, per-round
# keys and batch draws depend on the seed alone), so they can run as lanes of
# one vmapped scan instead of C sequential driver calls (DESIGN.md §7).
# ``jax.vmap`` returns a fresh function object per call, so jitting it anew
# on every sweep would miss the compile cache each time. The wrapper cache is
# a small MRU list keyed on scan_fn identity: repeated sweeps over
# caller-held scan_fns stay in steady state even when the caller alternates
# several of them — e.g. the attack-sweep benchmark's baseline, which cycles
# one prebuilt scan_fn per attack group every timed iteration and would
# recompile on every call under a 1-slot cache. Ad-hoc scan_fns (including
# ``run_matrix_vmapped``'s per-group builds, which are fresh objects each
# call and can never be re-looked-up) miss and age out; retention is bounded
# at ``_VMAPPED_CACHE_SIZE`` wrappers. (A weak/keyed map cannot do better:
# the wrapper closes over scan_fn, so any cache holding the wrapper pins
# its key.)

_VMAPPED_CACHE: list = []  # MRU-first [(scan_fn, config_key, vseg), ...]
_VMAPPED_CACHE_SIZE = 8


def _shard_sweep(vseg, mesh, lane_axis: str, worker_axis: str, *,
                 lane: bool, replicated: bool):
    """Wrap the vmapped sweep segment in ``shard_map`` over a 2-axis
    ``(lanes, workers)`` mesh (DESIGN.md §12): lanes are split over the lane
    axis (carry, mask schedule and the per-lane attack/agg plans), the batch
    schedule over the worker axis (the segment re-assembles the gradient
    stacks with a worker all_gather, exactly as on the 1-axis mesh path);
    levels and keys are replicated. Callers skip this wrap entirely on a
    1-device mesh — the bitwise contract by construction, as in
    ``_worker_gather``."""
    from jax.sharding import PartitionSpec as P

    lanes = P(lane_axis)
    # batch leaves: (L, m, n_max, ...) — or (R, L, m, ...) with a replicate
    # axis — split on the worker dim; masks lead with the lane (cell) axis
    batch_spec = P(None, None, worker_axis) if replicated \
        else P(None, worker_axis)
    xs_specs = (P(), batch_spec, lanes, P())
    in_specs = (lanes, xs_specs) + ((lanes, lanes) if lane else ())
    return jax.shard_map(vseg, mesh=mesh, in_specs=in_specs,
                         out_specs=(lanes, lanes),
                         axis_names={lane_axis, worker_axis}, check_vma=False)


def _vmapped_scan_fn(scan_fn, lane: bool = False, replicated: bool = False,
                     lane_mesh=None, lane_axis: str = "lanes",
                     worker_axis: str = "workers"):
    """Lane-batched segment fn: model/optimizer state and the mask schedule
    are mapped over the lane axis; levels / batches / keys stay shared (they
    depend only on the sweep seed) — crucially the ``lax.switch`` level index
    stays a scalar, keeping the one-branch-per-round dispatch. With ``lane``
    the segment's extra ``atk = (attack_id, theta)`` and ``agg = (agg_id,
    theta, thr_coeff)`` arguments are mapped over lanes as well (both
    dispatches are per-lane data; an absent axis is just ``None``, an empty
    pytree vmap maps over trivially).

    ``replicated`` nests a second vmap for the replicate axis (DESIGN.md
    §12): the outer map stays the cell axis above; the inner map runs each
    cell's replicates over per-replicate batch schedules (leading R axis),
    masks (cells carry a (C, R, T, n_max, m) schedule) and key streams
    ((R, T, 2)), while the level plan — and with it the ``lax.switch``
    index — stays scalar and shared, and the per-lane attack/agg plans stay
    per-cell. ``lane_mesh`` (2-axis, multi-device) additionally wraps the
    result in ``_shard_sweep``; a 1-device mesh is ignored here so the
    traced graph is the unsharded one (bitwise by construction)."""
    if lane_mesh is not None and \
            math.prod(list(lane_mesh.shape.values())) == 1:
        lane_mesh = None
    key = (lane, replicated, lane_mesh, lane_axis, worker_axis)
    for i, entry in enumerate(_VMAPPED_CACHE):
        if entry[0] is scan_fn and entry[1] == key:
            _VMAPPED_CACHE.insert(0, _VMAPPED_CACHE.pop(i))
            return entry[2]
    inner = scan_fn
    if replicated:
        rep_axes = ((0, 0), (None, 0, 0, 0))
        if lane:
            rep_axes = rep_axes + (None, None)
        inner = jax.vmap(scan_fn, in_axes=rep_axes)
    in_axes = ((0, 0), (None, None, 0, None))
    if lane:
        in_axes = in_axes + (0, 0)
    vseg = jax.vmap(inner, in_axes=in_axes)
    if lane_mesh is not None:
        vseg = _shard_sweep(vseg, lane_mesh, lane_axis, worker_axis,
                            lane=lane, replicated=replicated)
    vseg = jax.jit(vseg)
    _VMAPPED_CACHE.insert(0, (scan_fn, key, vseg))
    del _VMAPPED_CACHE[_VMAPPED_CACHE_SIZE:]
    return vseg


def _norm_lane_specs(specs):
    out = []
    for a in specs:
        name, kw = (a, {}) if isinstance(a, str) else (a[0], dict(a[1] or {}))
        out.append((name, kw))
    return out


def _lane_attack_plan(attacks):
    """Normalize per-lane attack specs (a name or ``(name, kwargs)``) into
    the compact dispatch plan: the tuple of distinct names in
    first-appearance order (the ``lax.switch`` branch set), the (C,) int32
    lane->branch index vector and the (C, N_PARAMS) parameter matrix."""
    specs = _norm_lane_specs(attacks)
    names = tuple(dict.fromkeys(name for name, _ in specs))
    ids = np.array([names.index(name) for name, _ in specs], np.int32)
    thetas = np.stack([attacks_lib.attack_theta(name, kw)
                       for name, kw in specs])
    return names, ids, thetas


def _lane_agg_plan(aggregators, cfg: DynaBROConfig):
    """The aggregator-axis analog of ``_lane_attack_plan``: distinct rule
    names (the ``agg_switch`` branch set), lane->branch ids, the
    (C, N_AGG_PARAMS) theta matrix — plus the (C,) fail-safe coefficient
    vector, because each lane's c_E follows its rule exactly as
    ``scenarios._cell_cfg`` sets it per cell: MFM runs the paper's
    δ-oblivious Option 2, every other rule Option 1 with ``cfg`` kappa."""
    specs = _norm_lane_specs(aggregators)
    names = tuple(dict.fromkeys(name for name, _ in specs))
    ids = np.array([names.index(name) for name, _ in specs], np.int32)
    thetas = np.stack([agg_engine.agg_theta(name, kw) for name, kw in specs])
    coeffs = np.array(
        [dataclasses.replace(
            cfg.mlmc, option=2 if name == "mfm" else 1).threshold_coeff
         for name, _ in specs], np.float32)
    return names, ids, thetas, coeffs


def run_dynabro_scan_sweep(
    grad_fn: GradFn,
    params,
    opt: Optimizer,
    cfg: DynaBROConfig,
    switchers,
    sample_batches: Callable[[int, int], Any],
    T: int,
    seed: int = 0,
    chunk: int = 0,
    scan_fn=None,
    vectorize_batches: bool = True,
    attacks=None,
    aggregators=None,
):
    """Run C = len(switchers) DynaBRO cells as one vmapped compiled loop.

    Every cell shares ``cfg`` / ``seed`` / ``sample_batches`` and differs
    only in its switcher — and, with ``attacks`` / ``aggregators``, in its
    attack and aggregation rule — so the level / key / batch schedules
    coincide and stay *un-batched* under ``vmap`` — in particular the
    ``lax.switch`` level dispatch keeps its scalar index (a batched index
    would degrade to execute-all-branches-and-select). Only the
    (C, T, n_max, m) mask schedule, the model/optimizer state and the
    per-lane attack/aggregator ids + parameters are batched over lanes.

    ``attacks`` (one spec per lane: a name or ``(name, kwargs)``) lets lanes
    differ in attack and attack kwargs: the sweep builds a per-lane (C,)
    attack-index vector into the compact set of distinct names plus a
    (C, N_PARAMS) parameter matrix (``attacks.attack_theta``), and the scan
    body dispatches each lane's attack via ``lax.switch`` over the uniform
    ``(stacked, mask, key, theta)`` implementations — under vmap this lowers
    to execute-all-branches-and-select, cheap because attacks are O(m·d)
    next to the per-worker gradient work. ``attacks=None`` keeps every lane
    on ``cfg.attack`` through the original static path, bitwise-unchanged.

    ``aggregators`` (same spec shape; kwargs are rule hyperparameters like
    ``delta`` / ``tau`` / ``multi`` / ``iters``) does the same for the
    aggregation rule via ``agg_engine.agg_switch`` over the uniform
    ``(stacked, n, theta)`` forms — so grids varying only an aggregator
    hyperparameter (CWTM at several δ) are free lanes, and each lane also
    carries its own fail-safe coefficient (MFM lanes run the Option-2 c_E,
    see ``_lane_agg_plan``). ``aggregators=None`` keeps every lane on
    ``cfg.aggregator`` through the static path, bitwise-unchanged.

    Mixed-rule grids are split **branch-homogeneously**: lanes are grouped
    by aggregator name (one sub-sweep per distinct rule, lanes permuted into
    groups and results un-permuted back to the caller's lane order), so each
    group's ``agg_switch`` has a single branch and skips the ``lax.switch``
    entirely — a 4-rule grid pays each rule's cost once per group instead of
    every lane paying all four under the vmapped switch's
    execute-all-branches-and-select (DESIGN.md §7). Grouping applies when
    ``scan_fn`` is None (one scan_fn built per group) or a *Mapping*
    ``{rule_name: scan_fn}`` with exactly the grid's distinct rule names as
    keys, each value a prebuilt ``make_dynabro_scan_fn(...,
    lane_aggregators=(rule_name,))`` (plus this sweep's attack names) — the
    steady-state form benchmarks use, since per-call rebuilt scan_fns miss
    ``_vmapped_scan_fn``'s identity-keyed cache. A plain prebuilt scan_fn
    runs the grid as one multi-branch dispatch, exactly as before.

    Returns ``[(params_c, logs_c), ...]`` in input order, each lane equal to
    the corresponding ``run_dynabro_scan(...)`` call with that lane's
    switcher, attack and aggregator — usually bitwise, always within the
    parity suite's 1e-6 tolerance (XLA may reorder float ops at ULP level
    when it fuses the batched body; the round logs match exactly — locked by
    tests/test_scenarios.py). ``scan_fn`` accepts a prebuilt *unsharded*
    ``make_dynabro_scan_fn`` result and must match both lane axes: built
    with ``lane_attacks=`` / ``lane_aggregators=`` equal to the distinct
    names (first-appearance order) this sweep derives, and without either
    when the corresponding axis is absent. The jitted vmap wrapper is
    memoized per scan_fn (``_vmapped_scan_fn``), so repeated sweeps with
    shared scan_fns reuse one compile cache.

    Thin wrapper over ``repro.api.Session.sweep`` driven by a validated
    ``repro.api.SweepSpec`` (DESIGN.md §10). The raw kwarg forms here remain
    a one-release compatibility layer; the ``{rule_name: scan_fn}`` mapping
    kwarg additionally warns — carry prebuilt group fns in
    ``SweepSpec.scan_fn`` instead.
    """
    from repro.api.session import Session
    from repro.api.specs import SweepSpec
    if isinstance(scan_fn, Mapping):
        warnings.warn(
            "passing scan_fn as a raw {rule_name: scan_fn} mapping kwarg is "
            "deprecated and will be removed after one release; carry it in "
            "repro.api.SweepSpec(..., scan_fn=...) and run "
            "Session.sweep(spec, T) (DESIGN.md §10)",
            DeprecationWarning, stacklevel=2)
    spec = SweepSpec(
        switchers=tuple(switchers),
        attacks=None if attacks is None else tuple(attacks),
        aggregators=None if aggregators is None else tuple(aggregators),
        scan_fn=scan_fn)
    sess = Session(cfg, grad_fn=grad_fn, params0=params, opt=opt,
                   sample_batches=sample_batches, seed=seed,
                   vectorize_batches=vectorize_batches)
    return sess.sweep(spec, T, chunk=chunk)
