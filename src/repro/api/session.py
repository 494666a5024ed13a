"""The session driver API (DESIGN.md §10): one entrypoint under every driver.

A ``Session`` binds what all the historical ``run_*`` drivers took as
positional sprawl — grad_fn, initial params, optimizer, config, switcher,
batch sampler, seed, sharding options — and exposes the round loop at every
granularity:

- ``init_carry()`` / ``step(carry, round_inputs)``: ONE round at a time
  through the same jitted compiled segment the batch drivers scan with.
  Segment chunking is bitwise-invariant (locked by tests/test_checkpoint.py
  and the chunk parity tests), so driving length-1 segments is
  bitwise-identical to a whole-``T`` ``run()`` — this is what lets the
  aggregation server (``repro.serve``) consume rounds at network cadence and
  still match the offline driver bit for bit.
- ``run(T)``: the batch drivers (compiled scan or the legacy per-round jit
  reference), exactly as ``run_dynabro`` / ``run_dynabro_scan`` /
  ``run_momentum`` / ``run_momentum_scan`` always behaved — those functions
  are now thin wrappers over a Session (exact-parity locked by the existing
  driver parity suite).
- ``sweep(spec, T)``: the lane-batched vmapped sweep over a validated
  ``SweepSpec`` (``run_dynabro_scan_sweep`` wraps this).

All compiled-loop machinery (``make_*_scan_fn``, schedule precomputes, lane
plans, the vmapped-wrapper cache) stays in ``core.robust_train`` — the
Session is the *driver*, not the kernel — and is always called through the
module (``rt.``) so tests and tools that monkeypatch those attributes keep
working.
"""
from __future__ import annotations

import contextlib
import dataclasses
import inspect
import os
from typing import Any, Callable, Dict, List, Mapping, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.api.specs import SweepSpec
from repro.core import robust_train as rt
from repro.core.mlmc import round_cost, sample_level
from repro.core.switching import Switcher
from repro.lint import runtime as sanitizers
from repro.optim.optimizers import Optimizer

GUARD_ENV = "REPRO_RECOMPILE_GUARD"


@dataclasses.dataclass(frozen=True)
class RoundSchedule:
    """The host-precomputed round schedule for ``T`` rounds — the same
    levels/masks/keys the compiled drivers scan over (DESIGN.md §5), exposed
    so per-round callers (the serve loop, replay tests) can draw from the
    identical stream. Momentum-mode schedules have ``n_max == 1`` and masks
    of shape (T, m); DynaBRO masks are (T, n_max, m) within-round masks."""

    T: int
    levels: np.ndarray  # (T,) MLMC level plan (zeros in momentum mode)
    ns: np.ndarray      # (T,) per-round unit counts
    n_max: int
    masks: np.ndarray   # (T, n_max, m) bool — or (T, m) in momentum mode
    keys: np.ndarray    # (T, 2) uint32 raw PRNG keys


@dataclasses.dataclass
class RoundInputs:
    """Everything one round consumes. ``batches`` is the n_max-padded
    per-worker batch tree (leading (m, n_max) axes; momentum mode: (m,) unit
    batches); ``masks`` the round's Byzantine-identity mask — mutable by
    design, the serve loop ORs straggler bits into it (a timed-out worker is
    just a dynamically-Byzantine one, DESIGN.md §10)."""

    t: int
    level: int
    batches: Any
    masks: Any  # (n_max, m) bool — or (m,) in momentum mode
    key: Any    # (2,) uint32


@dataclasses.dataclass
class StepInfo:
    """Per-round diagnostics from ``step``: the MLMC fail-safe verdict and
    correction norm (None in momentum mode, which has neither)."""

    failsafe_ok: Optional[bool] = None
    corr_norm: Optional[float] = None


class Session:
    """One bound training session; see the module docstring. Use
    ``build_session`` (or the ``run_*`` wrappers) rather than spelling out
    every field.

    ``mode`` is ``"dynabro"`` (Algorithm 2; needs ``opt``) or ``"momentum"``
    (the worker-momentum baseline; needs ``lr``/``beta``). Prebuilt
    ``scan_fn``s are validated against the session's mesh/microbatch/lane
    configuration up front, with the same errors the batch drivers raise.
    """

    def __init__(self, cfg, *, grad_fn, params0, opt: Optional[Optimizer] = None,
                 switcher: Optional[Switcher] = None,
                 sample_batches: Optional[Callable[[int, int], Any]] = None,
                 seed: int = 0, mode: str = "dynabro",
                 lr: Optional[float] = None, beta: Optional[float] = None,
                 scan_fn=None, vectorize_batches: bool = True,
                 mesh=None, worker_axis: str = "workers", param_specs=None,
                 microbatch: bool = False, m: Optional[int] = None,
                 guard_recompiles: Optional[bool] = None,
                 nan_tripwire: Optional[bool] = None,
                 sampler_factory: Optional[Callable[[int], Any]] = None):
        if mode not in ("dynabro", "momentum"):
            raise ValueError(
                f"unknown session mode {mode!r}; expected 'dynabro' or "
                f"'momentum'")
        if mode == "dynabro" and opt is None:
            raise ValueError("dynabro sessions need opt= (an Optimizer)")
        if mode == "momentum" and (lr is None or beta is None):
            raise ValueError("momentum sessions need lr= and beta=")
        self.cfg = cfg
        self.grad_fn = grad_fn
        self.params0 = params0
        self.opt = opt
        self.switcher = switcher
        self.sample_batches = sample_batches
        self.sampler_factory = sampler_factory
        self.seed = seed
        self.mode = mode
        self.lr, self.beta = lr, beta
        self.vectorize_batches = vectorize_batches
        self.mesh = mesh
        self.worker_axis = worker_axis
        self.param_specs = param_specs
        self.microbatch = microbatch
        self.m = m if m is not None else (switcher.m if switcher else None)
        # preflight validation, identical to the batch drivers' (and at the
        # same point: before any T<=0 early return a run() might take)
        if mesh is not None:
            if self.m is None:
                raise ValueError("mesh= needs a worker count: pass switcher= "
                                 "or m=")
            rt._check_worker_mesh(mesh, worker_axis, self.m,
                                  allow_model=(mode == "dynabro"))
        if scan_fn is not None:
            if mode == "dynabro":
                for lane_kind in ("lane_attacks", "lane_aggregators"):
                    if getattr(scan_fn, lane_kind, None) is not None:
                        raise ValueError(
                            f"scan_fn was built with {lane_kind}="
                            f"{getattr(scan_fn, lane_kind)!r}; that variant "
                            f"is for run_dynabro_scan_sweep(...), not "
                            f"run_dynabro_scan")
            rt._check_scan_fn_mesh(scan_fn, mesh)
            if mode == "dynabro":
                have_mb = getattr(scan_fn, "microbatch", microbatch)
                if have_mb != microbatch:
                    raise ValueError(
                        f"scan_fn was built with microbatch={have_mb}, but "
                        f"this run passes microbatch={microbatch}; rebuild "
                        f"the scan_fn to match (the two paths are not "
                        "bitwise-equivalent)")
        self._scan_fn = scan_fn
        self._schedules: Dict[int, RoundSchedule] = {}
        # runtime sanitizers (DESIGN.md §11): the recompile guard asserts a
        # compiled-segment signature seen once before never compiles again
        # (steady state — serve inherits this through step); the NaN tripwire
        # host-checks aggregator-facing outputs. Both default to their env
        # opt-ins (REPRO_RECOMPILE_GUARD / REPRO_NAN_TRIPWIRE).
        if guard_recompiles is None:
            guard_recompiles = os.environ.get(GUARD_ENV, "").lower() in (
                "1", "true", "on")
        self.guard_recompiles = guard_recompiles
        self.nan_tripwire = nan_tripwire
        self._steady_sigs: Set[Tuple] = set()

    # ------------------------------------------------------------ pieces

    @property
    def scan_fn(self):
        """The session's compiled segment fn, built on first use (via the
        ``rt`` module attribute, so monkeypatched builders are honored)."""
        if self._scan_fn is None:
            if self.mode == "dynabro":
                self._scan_fn = rt.make_dynabro_scan_fn(
                    self.grad_fn, self.cfg, self.opt, mesh=self.mesh,
                    worker_axis=self.worker_axis,
                    param_specs=self.param_specs, microbatch=self.microbatch)
            else:
                self._scan_fn = rt.make_momentum_scan_fn(
                    self.grad_fn, self.cfg, self.lr, self.beta,
                    mesh=self.mesh, worker_axis=self.worker_axis)
        return self._scan_fn

    def schedule(self, T: int) -> RoundSchedule:
        """The full host-side round schedule (cached per T) — exactly the
        precompute of the compiled batch drivers, so per-round stepping and
        ``run(T)`` draw from one stream."""
        sched = self._schedules.get(T)
        if sched is not None:
            return sched
        if self.switcher is None:
            raise ValueError("schedules need a switcher; build the session "
                             "with switcher=")
        if self.mode == "dynabro":
            levels, ns, n_max = rt._level_plan(
                self.cfg, np.random.default_rng(self.seed), T)
            masks = rt._mask_schedule(self.switcher, T, n_max, ns)
            keys = rt._np_prng_keys(
                self.seed * 100_003 + np.arange(T, dtype=np.int64))
        else:
            levels = np.zeros(T, np.int32)
            ns = np.ones(T, np.int64)
            n_max = 1
            masks = np.stack([self.switcher.mask(t) for t in range(T)])
            keys = rt._np_prng_keys(
                self.seed * 77_003 + np.arange(T, dtype=np.int64))
        sched = RoundSchedule(T, levels, ns, n_max, masks, keys)
        self._schedules[T] = sched
        return sched

    def init_carry(self):
        """The scan carry at round 0: ``(params, opt_state)`` (dynabro) or
        ``(params, worker_momenta)`` (momentum), device-placed per the
        session's sharding config."""
        params = self.params0
        if self.mode == "dynabro":
            if self.mesh is not None and "model" in self.mesh.axis_names:
                pin = rt._gspmd_constraints(self.mesh, self.worker_axis,
                                            self.param_specs)
                if pin is not None:
                    params = pin.put_params(params)
            return (params, self.opt.init(params))
        worker_m = jax.tree.map(
            lambda p: jnp.zeros((self.m,) + p.shape, jnp.float32), params)
        return (params, worker_m)

    def round_inputs(self, sched: RoundSchedule, t: int) -> RoundInputs:
        """Materialize round ``t``'s inputs from the schedule. Sampling is
        the direct per-round call — the reference the batch drivers'
        vectorized ``_batch_schedule`` is probe-checked against — so the
        padded batch tree is the one the offline scan consumes."""
        n = int(sched.ns[t])
        if self.mode == "dynabro":
            batches = rt._pad_units(self.sample_batches(t, n), sched.n_max,
                                    axis=1)
            return RoundInputs(t, int(sched.levels[t]), batches,
                               sched.masks[t], sched.keys[t])
        batches = jax.tree.map(lambda l: l[:, 0], self.sample_batches(t, 1))
        return RoundInputs(t, 0, batches, sched.masks[t], sched.keys[t])

    def _steady_guard(self, tag: str, xs, label: str):
        """A ``recompile_guard`` once this (tag, xs shapes/dtypes) signature
        has been seen (the first call with a signature is warmup: it may
        compile), else a null context that just records the signature."""
        if not self.guard_recompiles:
            return contextlib.nullcontext()
        sig: Tuple = (tag, self.mode) + tuple(jax.tree.leaves(
            jax.tree.map(lambda l: (tuple(l.shape), str(l.dtype)), xs)))
        if sig in self._steady_sigs:
            return sanitizers.recompile_guard(label)
        self._steady_sigs.add(sig)
        return contextlib.nullcontext()

    def step(self, carry, inputs: RoundInputs):
        """Advance one round: drive the compiled segment on a length-1
        schedule slice. Bitwise-identical to the same round inside a
        whole-``T`` ``run()`` (chunking invariance, DESIGN.md §5/§10).
        Returns ``(carry, StepInfo)``."""
        # every schedule() path emits int32 level plans (level_schedule and
        # the momentum zeros), so the step's trace signature is fixed a
        # priori — the old fallback consulted whichever schedule happened to
        # be cached first, tying the jit signature to cache insertion order
        one = lambda x: jnp.asarray(np.asarray(x)[None])  # noqa: E731
        if self.mode == "dynabro":
            xs = (jnp.asarray(np.asarray([inputs.level], dtype=np.int32)),
                  jax.tree.map(lambda l: jnp.asarray(l)[None], inputs.batches),
                  one(inputs.masks), one(inputs.key))
            with self._steady_guard("step", xs,
                                    f"Session.step (round {inputs.t})"):
                carry, (ok, dn) = self.scan_fn(carry, xs)
            info = StepInfo(failsafe_ok=bool(np.asarray(ok)[0]),
                            corr_norm=float(np.asarray(dn)[0]))
            sanitizers.maybe_assert_finite(
                {"params": carry[0], "corr_norm": dn},
                f"Session.step round {inputs.t}", enabled=self.nan_tripwire)
            return carry, info
        xs = (jax.tree.map(lambda l: jnp.asarray(l)[None], inputs.batches),
              one(inputs.masks), one(inputs.key))
        with self._steady_guard("step", xs,
                                f"Session.step (round {inputs.t})"):
            carry, _ = self.scan_fn(carry, xs)
        sanitizers.maybe_assert_finite(
            carry[0], f"Session.step round {inputs.t}",
            enabled=self.nan_tripwire)
        return carry, StepInfo()

    # ------------------------------------------------------------ drivers

    def run(self, T: int, *, eval_fn=None, eval_every: int = 0,
            chunk: int = 0, driver: str = "scan", step=None):
        """The whole-``T`` batch drivers. ``driver="scan"`` is the compiled
        chunked-``lax.scan`` loop; ``"legacy"`` the per-round jitted-step
        reference loop (the parity baseline — kept as a genuinely separate
        implementation). Returns ``(params, logs, evals)`` in dynabro mode
        and ``(params, evals)`` in momentum mode, exactly as the ``run_*``
        wrappers always did."""
        if driver not in ("scan", "legacy"):
            raise ValueError(
                f"unknown driver {driver!r}; expected 'scan' or 'legacy'")
        if driver == "legacy":
            if self.mesh is not None:
                raise ValueError("the legacy per-round driver runs unsharded;"
                                 " drop mesh= or use driver='scan'")
            if self.mode == "dynabro":
                return self._run_legacy_dynabro(T, eval_fn, eval_every, step)
            return self._run_legacy_momentum(T, eval_fn, eval_every, step)
        with obs.span("repro.run", rounds=T):
            if self.mode == "dynabro":
                return self._run_scan_dynabro(T, eval_fn, eval_every, chunk)
            return self._run_scan_momentum(T, eval_fn, eval_every, chunk)

    def _run_scan_dynabro(self, T, eval_fn, eval_every, chunk):
        if T <= 0:
            return self.params0, [], []
        with obs.span("repro.schedule"):
            sched = self.schedule(T)
            masks_dev = jnp.asarray(sched.masks)
            keys_dev = jnp.asarray(sched.keys)
            levels_dev = jnp.asarray(sched.levels)
        scan_fn = self.scan_fn
        carry = self.init_carry()
        oks, evals = [], []
        a = 0
        for b in rt._segment_bounds(T, eval_every if eval_fn else 0, chunk):
            batches = rt._batch_schedule(
                self.sample_batches, list(zip(range(a, b), sched.ns[a:b])),
                sched.n_max, vectorize=self.vectorize_batches)
            xs = (levels_dev[a:b], batches, masks_dev[a:b], keys_dev[a:b])
            with self._steady_guard("run", xs,
                                    f"Session.run segment [{a}:{b}]"), \
                    obs.span("repro.dispatch"):
                carry, (ok, _dn) = scan_fn(carry, xs)
            with obs.span("repro.wait"):
                oks.append(np.asarray(ok))
            sanitizers.maybe_assert_finite(
                carry[0], f"Session.run segment [{a}:{b}]",
                enabled=self.nan_tripwire)
            if eval_fn and eval_every and b % eval_every == 0:
                with obs.span("repro.eval"):
                    evals.append((b, eval_fn(carry[0], b - 1)))
            a = b
        with obs.span("repro.results"):
            ok_all = np.concatenate(oks) if oks else np.zeros(0, bool)
            logs = rt._round_logs(sched.levels, ok_all, sched.masks,
                                  self.cfg.mlmc.j_max)
        return carry[0], logs, evals

    def _run_scan_momentum(self, T, eval_fn, eval_every, chunk):
        if T <= 0:
            return self.params0, []
        with obs.span("repro.schedule"):
            sched = self.schedule(T)
            masks = jnp.asarray(sched.masks)  # (T, m)
            keys = jnp.asarray(sched.keys)
        scan_fn = self.scan_fn
        carry = self.init_carry()
        evals = []
        a = 0
        for b in rt._segment_bounds(T, eval_every if eval_fn else 0, chunk):
            bsched = rt._batch_schedule(self.sample_batches,
                                        [(t, 1) for t in range(a, b)], 1,
                                        vectorize=self.vectorize_batches)
            batches = jax.tree.map(lambda l: l[:, :, 0], bsched)  # (L, m, ...)
            xs = (batches, masks[a:b], keys[a:b])
            with self._steady_guard("run", xs,
                                    f"Session.run segment [{a}:{b}]"), \
                    obs.span("repro.dispatch"):
                carry, _ = scan_fn(carry, xs)
            sanitizers.maybe_assert_finite(
                carry[0], f"Session.run segment [{a}:{b}]",
                enabled=self.nan_tripwire)
            if eval_fn and eval_every and b % eval_every == 0:
                with obs.span("repro.eval"):
                    evals.append((b, eval_fn(carry[0], b - 1)))
            a = b
        return carry[0], evals

    def _run_legacy_dynabro(self, T, eval_fn, eval_every, step):
        cfg, opt = self.cfg, self.opt
        rng = np.random.default_rng(self.seed)
        step = step or rt.make_dynabro_step(self.grad_fn, cfg, opt)
        params = self.params0
        opt_state = opt.init(params)
        logs, evals = [], []
        for t in range(T):
            j = sample_level(rng, cfg.mlmc.j_max) if cfg.use_mlmc else 0
            n = 2 ** j if (cfg.use_mlmc and j <= cfg.mlmc.j_max) else 1
            masks = np.stack([self.switcher.within_round(t, k)
                              for k in range(n)])
            batches = self.sample_batches(t, n)
            key = jax.random.PRNGKey(self.seed * 100_003 + t)
            params, opt_state, info = step(params, opt_state, batches,
                                           jnp.asarray(masks), key, j)
            logs.append(rt.RoundLog(j, bool(info["failsafe_ok"]),
                                    int(masks[0].sum()),
                                    round_cost(j, cfg.mlmc.j_max)))
            if eval_fn and eval_every and (t + 1) % eval_every == 0:
                evals.append((t + 1, eval_fn(params, t)))
        return params, logs, evals

    def _run_legacy_momentum(self, T, eval_fn, eval_every, step):
        step = step or rt.make_momentum_step(self.grad_fn, self.cfg, self.lr,
                                             self.beta)
        params = self.params0
        worker_m = jax.tree.map(
            lambda p: jnp.zeros((self.switcher.m,) + p.shape, jnp.float32),
            params)
        evals = []
        for t in range(T):
            mask = self.switcher.mask(t)
            batches = jax.tree.map(lambda l: l[:, 0],
                                   self.sample_batches(t, 1))
            key = jax.random.PRNGKey(self.seed * 77_003 + t)
            params, worker_m = step(params, worker_m, batches,
                                    jnp.asarray(mask), key)
            if eval_fn and eval_every and (t + 1) % eval_every == 0:
                evals.append((t + 1, eval_fn(params, t)))
        return params, evals

    # ------------------------------------------------------------- sweep

    def _sampler_for(self, seed: int):
        """The batch sampler of one replicate stream: ``sampler_factory``
        when the session carries one, else the bound ``sample_batches`` —
        valid only for the session's own seed, because per-replicate data
        streams must differ (DESIGN.md §12)."""
        if self.sampler_factory is not None:
            return self.sampler_factory(seed)
        if seed == self.seed:
            return self.sample_batches
        raise ValueError(
            "per-replicate batch streams need sampler_factory= (seed -> "
            "sample_batches); build the session with sampler_factory=, or "
            "via build_session with a Task whose make_sampler accepts "
            "sampler_seed=")

    def _sweep_streams(self, spec: SweepSpec, T: int):
        """The host-side schedule precompute shared by ``sweep`` and
        ``sweep_halving``: the session-seed level plan plus the
        per-replicate mask / key / batch streams (DESIGN.md §12). Masks come
        back ``(C, T, n_max, m)`` — or ``(C, R, T, n_max, m)`` when the spec
        replicates — keys ``(T, 2)`` / ``(R, T, 2)``."""
        cfg = self.cfg
        C = spec.lanes
        R = spec.n_replicates
        rep_seeds = spec.replicate_seeds(self.seed)
        replicated = R > 1
        levels, ns, n_max = rt._level_plan(
            cfg, np.random.default_rng(self.seed), T)
        sw_reps = [spec.resolve_switchers(self.m, s) for s in rep_seeds]
        if replicated:
            masks = np.stack([
                np.stack([rt._mask_schedule(sws[c], T, n_max, ns)
                          for sws in sw_reps])  # (R, T, n_max, m)
                for c in range(C)])              # -> (C, R, T, n_max, m)
            keys = np.stack([
                rt._np_prng_keys(s * 100_003 + np.arange(T, dtype=np.int64))
                for s in rep_seeds])             # (R, T, 2)
        else:
            masks = np.stack([rt._mask_schedule(sw, T, n_max, ns)
                              for sw in sw_reps[0]])
            keys = rt._np_prng_keys(
                rep_seeds[0] * 100_003 + np.arange(T, dtype=np.int64))
        samplers = [self._sampler_for(s) for s in rep_seeds]
        return (levels, ns, n_max, masks, keys, samplers, replicated,
                sw_reps[0][0].m if sw_reps[0] else self.m)

    def _sweep_batches(self, samplers, a: int, b: int, ns, n_max: int,
                       replicated: bool):
        """One segment's padded batch schedule: per-replicate schedules are
        stacked on a leading R axis (the inner vmap's mapped axis)."""
        tn = list(zip(range(a, b), ns[a:b]))
        if not replicated:
            return rt._batch_schedule(samplers[0], tn, n_max,
                                      vectorize=self.vectorize_batches)
        per_rep = [rt._batch_schedule(s, tn, n_max,
                                      vectorize=self.vectorize_batches)
                   for s in samplers]
        return jax.tree.map(lambda *ls: jnp.stack(ls), *per_rep)

    def _sweep_scan_fn(self, spec_scan_fn, cfg, atk_names, agg_names,
                       lane_mesh, lane_axis: str):
        """Build — or validate — the sweep's segment fn against the derived
        lane-axis branch sets and the (normalized) lane mesh."""
        lm = rt._norm_mesh(lane_mesh)
        if spec_scan_fn is None:
            return rt.make_dynabro_scan_fn(
                self.grad_fn, cfg, self.opt, lane_attacks=atk_names,
                lane_aggregators=agg_names, sweep_mesh=lm,
                lane_axis=lane_axis, worker_axis=self.worker_axis), lm
        scan_fn = spec_scan_fn
        if getattr(scan_fn, "worker_mesh", None) is not None:
            raise ValueError(
                "scan_fn was built with mesh=; vmapped sweeps run "
                "unsharded (DESIGN.md §7) — rebuild it without mesh")
        have_sm = rt._norm_mesh(getattr(scan_fn, "sweep_mesh", None))
        if have_sm != lm:
            raise ValueError(
                f"scan_fn was built with sweep_mesh={have_sm}, but this "
                f"sweep passes lane_mesh={lm}; rebuild it with "
                f"make_dynabro_scan_fn(..., sweep_mesh=...) to match")
        # the lane ids index the derived name tuples; a scan_fn whose
        # lax.switch branch order differs — or that lacks/adds a lane
        # axis — would silently apply the wrong attack or rule per lane
        for kind, want, arg in (
                ("lane_attacks", atk_names, "attacks"),
                ("lane_aggregators", agg_names, "aggregators")):
            have = getattr(scan_fn, kind, None)
            if have == want:
                continue
            if want is None:
                raise ValueError(
                    f"scan_fn was built with {kind}={have!r} but this "
                    f"sweep passes no {arg}; rebuild it without {kind} "
                    f"(or pass the per-lane {arg})")
            raise ValueError(
                f"scan_fn was built with {kind}={have!r} but this "
                f"sweep's {arg} derive {want!r}; rebuild it with "
                f"make_dynabro_scan_fn(..., {kind}={want!r})")
        return scan_fn, lm

    def _check_sweep_lane_mesh(self, lane_mesh, lane_axis: str, C: int,
                               m: Optional[int]):
        if lane_mesh is None:
            return
        rt._check_lane_mesh(lane_mesh, lane_axis, self.worker_axis, m)
        n_lanes = lane_mesh.shape[lane_axis]
        if C % n_lanes:
            raise ValueError(
                f"sweep cell count C={C} not divisible by the "
                f"{lane_axis!r} mesh axis size {n_lanes}")

    def sweep(self, spec: SweepSpec, T: int, *, chunk: int = 0,
              lane_chunk: int = 0, lane_mesh=None,
              lane_axis: str = "lanes") -> List[Any]:
        """Run ``spec.lanes`` cells as lanes of ONE vmapped compiled loop —
        the body behind ``run_dynabro_scan_sweep`` (see its docstring for
        the full lane/grouping/parity contracts, DESIGN.md §7). Mixed-rule
        grids recurse into branch-homogeneous sub-sweeps; results come back
        in the caller's lane order.

        With spec ``seeds=`` / ``replicates=`` every cell additionally runs
        one lane per replicate seed (DESIGN.md §12): masks, attack keys and
        batch draws follow the replicate seed (batches through the session's
        ``sampler_factory``), the MLMC level plan stays the session seed's
        (replicates are level-paired across cells), and the return value
        becomes a list over cells of per-replicate ``(params, logs)`` lists.
        With one replicate the flat ``[(params, logs), ...]`` shape — and,
        for the session's own seed, the exact schedule stream — of the
        un-replicated sweep is preserved.

        Per-lane finals are host ``numpy`` arrays: each sub-sweep (a chunk
        or a rule group) copies its stacked final params to the host once,
        one transfer per leaf, and each lane's params are read-only views of
        that copy, bitwise the lane's slice of the device carry (with
        ``T <= 0``, every lane gets ``params0`` on the host). Round logs are
        built for all lanes of a sub-sweep in one pass.

        ``lane_chunk`` streams grids through fixed-size cell chunks (at most
        ``lane_chunk`` cells per dispatch, results accumulated host-side in
        caller order — chunking is bitwise-invariant, locked by
        tests/test_replicates.py). ``lane_mesh`` (a 2-axis
        ``launch.mesh.make_lane_mesh`` mesh) shards the cell axis — and,
        with a multi-device worker axis, each lane's per-worker gradients —
        across devices; a 1-device mesh is bitwise-identical to unsharded by
        construction. Requires the cell count divisible by the lane axis
        (per chunk, when combined with ``lane_chunk``)."""
        if self.mode != "dynabro":
            raise ValueError("sweeps are dynabro-mode only")
        spec = spec if isinstance(spec, SweepSpec) else SweepSpec(**spec)
        with obs.span("repro.sweep", lanes=spec.lanes * spec.n_replicates,
                      rounds=T):
            return self._sweep(spec, T, chunk, lane_chunk, lane_mesh,
                               lane_axis)

    def _sweep(self, spec: SweepSpec, T: int, chunk: int, lane_chunk: int,
               lane_mesh, lane_axis: str) -> List[Any]:
        """The body of ``sweep``; chunks and rule groups recurse here."""
        cfg, opt, params = self.cfg, self.opt, self.params0
        C = spec.lanes
        R = spec.n_replicates
        replicated = R > 1
        if C == 0:
            return []
        if T <= 0:
            params = jax.device_get(params)
            return [[(params, [])] * R for _ in range(C)] if replicated \
                else [(params, []) for _ in range(C)]

        # ---- fixed-size lane chunks (DESIGN.md §12): split the cell axis
        # up front and accumulate per-chunk results host-side, so 1000+-cell
        # grids stream through bounded dispatches instead of one giant one
        if lane_chunk and lane_chunk > 0 and C > lane_chunk:
            outs: List[Any] = []
            for a in range(0, C, lane_chunk):
                sub = spec.lane_subset(range(a, min(a + lane_chunk, C)),
                                       scan_fn=spec.scan_fn)
                with obs.span("repro.sweep.chunk",
                              lanes=sub.lanes * R):
                    outs.extend(self._sweep(sub, T, chunk, 0, lane_mesh,
                                            lane_axis))
            return outs

        attacks = spec.attack_lanes()
        aggregators = spec.agg_lanes()
        scan_fn = spec.scan_fn

        # ---- branch-homogeneous lane grouping (DESIGN.md §7): split a
        # mixed-rule grid into one sub-sweep per distinct aggregator name, in
        # first-appearance order, and scatter results back to caller lane
        # order. Every schedule a sub-sweep derives (levels, keys, batches)
        # is a pure function of (cfg, seed, T), so the groups share them by
        # construction.
        group_fns = None
        if isinstance(scan_fn, Mapping):
            if aggregators is None:
                raise ValueError(
                    "scan_fn given as a {rule_name: scan_fn} mapping but "
                    "this sweep passes no aggregators to group by")
            group_fns = scan_fn
        if aggregators is not None:
            distinct = tuple(dict.fromkeys(name for name, _ in aggregators))
            # a superset mapping is fine — lane_chunk sub-sweeps may see only
            # a subset of the full grid's rules — but a missing key is a typo
            if group_fns is not None and not set(distinct) <= set(group_fns):
                raise ValueError(
                    f"scan_fn mapping keys {sorted(group_fns)} do not cover "
                    f"the grid's distinct aggregator names "
                    f"{sorted(distinct)}")
            if len(distinct) > 1 and (scan_fn is None
                                      or group_fns is not None):
                outs = [None] * C
                for name in distinct:
                    idx = [c for c in range(C)
                           if aggregators[c][0] == name]
                    with obs.span("repro.sweep.group", lanes=len(idx) * R):
                        sub = self._sweep(
                            spec.lane_subset(
                                idx, scan_fn=(None if group_fns is None
                                              else group_fns[name])),
                            T, chunk, 0, lane_mesh, lane_axis)
                    for j, c in enumerate(idx):
                        outs[c] = sub[j]
                return outs
            if group_fns is not None:  # single distinct rule: unwrap and run
                scan_fn = group_fns[distinct[0]]

        with obs.span("repro.schedule"):
            (levels, ns, n_max, masks, keys, samplers, replicated,
             m) = self._sweep_streams(spec, T)
            self._check_sweep_lane_mesh(lane_mesh, lane_axis, C, m)
            atk = agg = atk_names = agg_names = None
            if attacks is not None:
                atk_names, ids, thetas = rt._lane_attack_plan(attacks)
                atk = (jnp.asarray(ids), jnp.asarray(thetas))
            if aggregators is not None:
                agg_names, gids, gthetas, coeffs = rt._lane_agg_plan(
                    aggregators, cfg)
                agg = (jnp.asarray(gids), jnp.asarray(gthetas),
                       jnp.asarray(coeffs))
            masks_dev, keys_dev = jnp.asarray(masks), jnp.asarray(keys)
            levels_dev = jnp.asarray(levels)
        lane_mode = atk is not None or agg is not None
        scan_fn, lm = self._sweep_scan_fn(scan_fn, cfg, atk_names, agg_names,
                                          lane_mesh, lane_axis)
        misses = rt._VMAPPED_MISSES
        vseg = rt._vmapped_scan_fn(scan_fn, lane=lane_mode,
                                   replicated=replicated, lane_mesh=lm,
                                   lane_axis=lane_axis,
                                   worker_axis=self.worker_axis)
        traced = rt._VMAPPED_MISSES - misses

        def lanes(tree):  # identical initial state in every lane
            lead = (C, R) if replicated else (C,)
            return jax.tree.map(
                lambda l: jnp.broadcast_to(l, lead + l.shape), tree)

        carry = (lanes(params), lanes(opt.init(params)))

        oks = []
        a = 0
        for b in rt._segment_bounds(T, 0, chunk):
            batches = self._sweep_batches(samplers, a, b, ns, n_max,
                                          replicated)
            if replicated:
                xs = (levels_dev[a:b], batches, masks_dev[:, :, a:b],
                      keys_dev[:, a:b])
            else:
                xs = (levels_dev[a:b], batches, masks_dev[:, a:b],
                      keys_dev[a:b])
            with obs.span("repro.dispatch", traced=traced):
                if lane_mode:
                    carry, (ok, _dn) = vseg(carry, xs, atk, agg)
                else:
                    carry, (ok, _dn) = vseg(carry, xs)
            traced = 0
            with obs.span("repro.wait"):
                oks.append(np.asarray(ok))  # (C, [R,] b - a)
            a = b
        with obs.span("repro.results", lanes=C * R) as s:
            return _lane_results(carry[0], levels,
                                 np.concatenate(oks, axis=-1), masks,
                                 cfg.mlmc.j_max, s)

    def sweep_halving(self, spec: SweepSpec, T: int, *,
                      objective: Callable[[Any], float],
                      keep: float = 0.5, rungs=None, lane_mesh=None,
                      lane_axis: str = "lanes",
                      min_cells: int = 1) -> List[Dict[str, Any]]:
        """Adaptive successive-halving sweep (DESIGN.md §12): run every cell,
        and at each rung boundary prune the worst cells — scored by the mean
        of ``objective(params)`` (lower is better) over the cell's replicate
        lanes — keeping a ``keep`` fraction (at least ``min_cells``; NaN
        scores prune first). Survivors continue with their carries sliced to
        the surviving lanes, so a survivor's trajectory is bitwise-identical
        to a plain sweep of the surviving subset (lane-subset invariance,
        locked by tests/test_replicates.py).

        ``rungs`` is the increasing list of round counts at which to prune
        (default: one prune at ``T // 2``). Mixed-rule grids run as one
        multi-branch dispatch (no branch-homogeneous grouping — pruning
        scores are global across rules). Returns one dict per cell, in
        caller order: ``{"pruned": bool, "rounds_run": int, "results":
        [(params, logs), ...]}`` with one entry per replicate; a pruned
        cell's results are its state at the rung that dropped it."""
        if self.mode != "dynabro":
            raise ValueError("sweeps are dynabro-mode only")
        spec = spec if isinstance(spec, SweepSpec) else SweepSpec(**spec)
        if isinstance(spec.scan_fn, Mapping):
            raise ValueError(
                "sweep_halving runs mixed-rule grids as one multi-branch "
                "dispatch; pass a plain scan_fn (or None), not a "
                "{rule: scan_fn} mapping")
        cfg = self.cfg
        C = spec.lanes
        R = spec.n_replicates
        if C == 0:
            return []
        if T <= 0:
            raise ValueError("sweep_halving needs T >= 1")
        if not 0.0 < keep <= 1.0:
            raise ValueError(f"keep= must be in (0, 1], got {keep}")
        if rungs is None:
            rungs = [T // 2] if T >= 2 else []
        rungs = [int(r) for r in rungs]
        if any(not 0 < r < T for r in rungs) or \
                any(b <= a for a, b in zip(rungs, rungs[1:])):
            raise ValueError(
                f"rungs= must be strictly increasing round counts in "
                f"(0, T={T}), got {rungs}")
        with obs.span("repro.sweep_halving", lanes=C * R, rounds=T):
            return self._sweep_halving(spec, T, objective, keep, rungs,
                                       lane_mesh, lane_axis, min_cells)

    def _sweep_halving(self, spec: SweepSpec, T: int, objective, keep: float,
                       rungs: List[int], lane_mesh, lane_axis: str,
                       min_cells: int) -> List[Dict[str, Any]]:
        """The body of ``sweep_halving``, past its checks."""
        cfg = self.cfg
        C = spec.lanes
        R = spec.n_replicates
        attacks = spec.attack_lanes()
        aggregators = spec.agg_lanes()
        with obs.span("repro.schedule"):
            (levels, ns, n_max, masks, keys, samplers, replicated,
             m) = self._sweep_streams(spec, T)
            self._check_sweep_lane_mesh(lane_mesh, lane_axis, C, m)
            atk = agg = atk_names = agg_names = None
            if attacks is not None:
                atk_names, ids, thetas = rt._lane_attack_plan(attacks)
                atk = (jnp.asarray(ids), jnp.asarray(thetas))
            if aggregators is not None:
                agg_names, gids, gthetas, coeffs = rt._lane_agg_plan(
                    aggregators, cfg)
                agg = (jnp.asarray(gids), jnp.asarray(gthetas),
                       jnp.asarray(coeffs))
            masks_dev, keys_dev = jnp.asarray(masks), jnp.asarray(keys)
            levels_dev = jnp.asarray(levels)
        lane_mode = atk is not None or agg is not None
        scan_fn, lm = self._sweep_scan_fn(spec.scan_fn, cfg, atk_names,
                                          agg_names, lane_mesh, lane_axis)
        misses = rt._VMAPPED_MISSES
        vseg = rt._vmapped_scan_fn(scan_fn, lane=lane_mode,
                                   replicated=replicated, lane_mesh=lm,
                                   lane_axis=lane_axis,
                                   worker_axis=self.worker_axis)
        traced = rt._VMAPPED_MISSES - misses
        n_lanes_mesh = lm.shape[lane_axis] if lm is not None else 1

        def lanes(tree):
            lead = (C, R) if replicated else (C,)
            return jax.tree.map(
                lambda l: jnp.broadcast_to(l, lead + l.shape), tree)

        def take(tree, idx):
            return jax.tree.map(lambda l: l[jnp.asarray(idx)], tree)

        def cell_outs(carry, ok_rows, alive, span):
            """Per live cell, its (params, logs) per replicate."""
            outs = _lane_results(carry[0], levels[:ok_rows.shape[-1]],
                                 ok_rows, masks[np.asarray(alive)],
                                 cfg.mlmc.j_max, span)
            return outs if replicated else [[o] for o in outs]

        carry = (lanes(self.params0), lanes(self.opt.init(self.params0)))
        alive = list(range(C))  # original cell index per live lane
        outs: List[Optional[Dict[str, Any]]] = [None] * C
        oks: List[np.ndarray] = []
        a = 0
        for b in rungs + [T]:
            batches = self._sweep_batches(samplers, a, b, ns, n_max,
                                          replicated)
            if replicated:
                xs = (levels_dev[a:b], batches,
                      masks_dev[jnp.asarray(alive)][:, :, a:b],
                      keys_dev[:, a:b])
            else:
                xs = (levels_dev[a:b], batches,
                      masks_dev[jnp.asarray(alive)][:, a:b], keys_dev[a:b])
            with obs.span("repro.dispatch", traced=traced):
                if lane_mode:
                    carry, (ok, _dn) = vseg(carry, xs, atk, agg)
                else:
                    carry, (ok, _dn) = vseg(carry, xs)
            traced = 0
            with obs.span("repro.wait"):
                oks.append(np.asarray(ok))
            ok_all = np.concatenate(oks, axis=-1)  # (C_live, [R,] b)
            if b == T:
                break
            # ---- prune: mean objective over replicates, lower is better
            with obs.span("repro.results", lanes=len(alive) * R) as s:
                results = cell_outs(carry, ok_all, alive, s)
            finals = np.array([[float(objective(p)) for p, _ in cell]
                               for cell in results])
            scores = np.where(np.isnan(finals), np.inf, finals).mean(axis=1)
            k = max(int(min_cells), int(np.ceil(len(alive) * keep)))
            if n_lanes_mesh > 1:  # keep the lane axis divisible
                k = max(n_lanes_mesh,
                        int(np.ceil(k / n_lanes_mesh)) * n_lanes_mesh)
            k = min(k, len(alive))
            order = np.argsort(scores, kind="stable")
            keep_local = sorted(int(j) for j in order[:k])
            if len(keep_local) < len(alive):
                for j, cell in enumerate(alive):
                    if j not in set(keep_local):
                        outs[cell] = {"pruned": True, "rounds_run": b,
                                      "results": results[j]}
                carry = (take(carry[0], keep_local),
                         take(carry[1], keep_local))
                if lane_mode:
                    atk = None if atk is None else take(atk, keep_local)
                    agg = None if agg is None else take(agg, keep_local)
                oks = [o[np.asarray(keep_local)] for o in oks]
                alive = [alive[j] for j in keep_local]
            a = b
        with obs.span("repro.results", lanes=len(alive) * R) as s:
            results = cell_outs(carry, ok_all, alive, s)
        for cell, res in zip(alive, results):
            outs[cell] = {"pruned": False, "rounds_run": T, "results": res}
        return outs


def _lane_results(params, levels, ok, masks, j_max: int, span) -> list:
    """Per-lane ``(params, logs)`` of one sub-sweep, nested over the lane
    dims ``ok.shape[:-1]`` — ``(C,)`` or ``(C, R)`` — as ``sweep`` returns
    them. The stacked final ``params`` come to the host in ONE copy, one
    transfer per leaf (``span`` counts the leaves under ``copies``); each
    lane's params are read-only numpy views of that copy, and the round logs
    of every lane come from one ``rt._round_logs_lanes`` pass."""
    leaves, treedef = jax.tree.flatten(jax.device_get(params))
    span.add("copies", len(leaves))
    for leaf in leaves:
        leaf.flags.writeable = False  # views of it inherit the flag
    lead = ok.shape[:-1]
    flat = list(zip((treedef.unflatten([leaf[i] for leaf in leaves])
                     for i in np.ndindex(*lead)),
                    rt._round_logs_lanes(levels, ok, masks, j_max)))
    if len(lead) == 2:
        R = lead[1]
        return [flat[c:c + R] for c in range(0, len(flat), R)]
    return flat


def _task_sampler_factory(task, m: int):
    """A seed -> sampler factory from a Task whose ``make_sampler`` accepts
    ``sampler_seed=`` (the replicate-axis data-stream hook, DESIGN.md §12);
    ``None`` when the task cannot re-seed its sampler."""
    try:
        params = inspect.signature(task.make_sampler).parameters
    except (TypeError, ValueError):
        return None
    if "sampler_seed" not in params:
        return None
    return lambda s: task.make_sampler(m, sampler_seed=s)


def build_session(cfg, task=None, *, m: Optional[int] = None,
                  switcher: Optional[Switcher] = None, **kw) -> Session:
    """The facade constructor: ``build_session(cfg, task) -> Session``.

    ``task`` (a ``scenarios.Task``) supplies ``grad_fn`` / ``params0`` and —
    given a worker count via ``m=`` or ``switcher=`` — the batch sampler
    (plus, when ``task.make_sampler`` accepts ``sampler_seed=``, the
    per-replicate ``sampler_factory`` the sweep's seed axis needs); any
    Session kwarg can override or extend it. Without a task, pass
    ``grad_fn=`` / ``params0=`` / ``sample_batches=`` directly."""
    if m is None and switcher is not None:
        m = switcher.m
    if task is not None:
        kw.setdefault("grad_fn", task.grad_fn)
        kw.setdefault("params0", task.params0)
        if m is not None:
            kw.setdefault("sample_batches", task.make_sampler(m))
            factory = _task_sampler_factory(task, m)
            if factory is not None:
                kw.setdefault("sampler_factory", factory)
    return Session(cfg, switcher=switcher, m=m, **kw)
