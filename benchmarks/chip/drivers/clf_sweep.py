"""The scenario grid: every lane of attack x switcher x rule x replicate
runs Algorithm 2 on the classification testbed, through ``Session.sweep``.

The timed path is the program's lane-batched sweep (``api/session.py``,
``core/robust_train.py::make_dynabro_scan_fn``) over the whole grid in
``lane_chunk``-cell dispatches, with the prebuilt ``{rule: scan_fn}``
mapping that keeps every chunk on one compiled program. The model is the
program's classification loss (``data/classification.py::clf_loss``); its
data, weights and per-replicate batch draws come from the seed. Set-up
runs the grid once, which compiles every chunk's program; the window runs
whole grids until the deadline has passed.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.chip import generator
from benchmarks.chip.reference import clf as clf_ref


class Driver:
    def __init__(self, run, devices):
        self.run, self.devices = run, devices
        self.config, self.grid = run.config, run.traffic
        self.cells = generator.grid_cells(self.grid)
        self.n_rep = int(self.grid["replicates"])
        self.T = int(self.config["mlmc"]["T"])
        self.m = int(self.config["workers"])
        self.last = None
        self.feed = self.sampler

    def sampler(self, rep_seed: int):
        """Replicate ``rep_seed``'s batch draws: ``(t, n)`` -> (m, n,
        unit_batch) training indices. The program is fed through
        ``self.feed``, the reference reads this."""
        import jax

        key = jax.device_put(generator.device_key(rep_seed, generator.DATA),
                             self.devices[0])
        m, ub = self.m, int(self.config["unit_batch"])
        n_train = int(self.config["data"]["n_train"])
        return lambda t, n: generator.unit_indices(key, t, m, n, ub, n_train)

    def build(self):
        """The dataset (the configuration's, fixed by its own seed, as the
        paper's datasets are fixed), the task and one compiled program per
        rule; nothing here depends on ``--seed``."""
        import jax

        if self.config["matmul_precision"] != "highest":
            raise ValueError("the driver runs float32 at highest precision")
        # the configuration's float32: matrix products at full precision
        jax.config.update("jax_default_matmul_precision", "highest")
        from repro.api import (DynaBROConfig, MLMCConfig, Session, SweepSpec,
                               make_dynabro_scan_fn, sgd)
        from repro.data.classification import clf_loss

        cfg, grid, dev = self.config, self.grid, self.devices[0]
        Xtr, ytr, _, _ = generator.mixture_dataset(int(cfg["data"]["seed"]),
                                                   cfg["data"])
        self.X, self.y = jax.device_put(Xtr, dev), jax.device_put(ytr, dev)
        X, y = self.X, self.y
        sizes = cfg["model"]["sizes"]
        self.init = jax.jit(lambda k: generator.mlp_weights(k, sizes))

        def grad_fn(params, idx):
            return jax.grad(clf_loss)(params, (X[idx], y[idx]))

        mc = cfg["mlmc"]
        dcfg = DynaBROConfig(
            mlmc=MLMCConfig(T=self.T, m=self.m, V=float(mc["V"]), option=1,
                            kappa=float(mc["kappa"]), j_cap=int(mc["j_cap"])),
            aggregator=grid["rules"][0]["name"], attack="none")
        opt = sgd(float(cfg["optimizer"]["lr"]))
        attacks = tuple((a, kw) for a, kw, _, _, _ in self.cells)
        # the sweep's attack branch order: distinct names, first appearance
        attack_names = tuple(dict.fromkeys(a for a, _ in attacks))
        self.group_fns = {r["name"]: make_dynabro_scan_fn(
            grad_fn, dcfg, opt, lane_attacks=attack_names,
            lane_aggregators=(r["name"],)) for r in grid["rules"]}
        self.session_seed = int(grid["session_seed"])
        self._session = lambda params0, rep_seeds: Session(
            dcfg, grad_fn=grad_fn, params0=params0, opt=opt, m=self.m,
            sample_batches=self.feed(rep_seeds[0]),
            seed=self.session_seed, sampler_factory=self.feed)
        self._spec = lambda rep_seeds: SweepSpec(
            switchers=tuple(("periodic", dict(n_byz=int(grid["n_byz"]), K=K))
                            for _, _, K, _, _ in self.cells),
            attacks=attacks,
            aggregators=tuple((r, kw) for _, _, _, r, kw in self.cells),
            seeds=rep_seeds, scan_fn=self.group_fns)

    def prepare(self):
        """Weights, replicate seeds and checked lanes from the seed; one
        whole grid, which warms every chunk's program."""
        import jax

        seed = self.run.seed
        self.params0 = self.init(jax.device_put(
            generator.device_key(seed, generator.WEIGHTS), self.devices[0]))
        self.rep_seeds = generator.replicate_seeds(seed, self.n_rep)
        self.spec = self._spec(self.rep_seeds)
        self.session = self._session(self.params0, self.rep_seeds)
        self.lanes_checked = self._pick_lanes()
        self._sweep()

    def _pick_lanes(self):
        """One (cell, replicate) per rule, drawn from the seed."""
        rng = generator.host_rng(self.run.seed, generator.SCENARIO, 1)
        out = []
        for rule in self.grid["rules"]:
            cells = [c for c, cell in enumerate(self.cells)
                     if cell[3] == rule["name"]]
            out.append((int(rng.choice(cells)),
                        int(rng.integers(0, self.n_rep))))
        return out

    def _sweep(self):
        import jax

        with self.run.span("sweep"):
            outs = self.session.sweep(self.spec, self.T,
                                      lane_chunk=int(self.grid["lane_chunk"]))
            jax.block_until_ready([p for cell in outs for p, _ in cell])
        self.last = {(c, r): (jax.tree.map(np.asarray, outs[c][r][0]),
                              [lg.failsafe_ok for lg in outs[c][r][1]],
                              [lg.level for lg in outs[c][r][1]])
                     for c, r in self.lanes_checked}
        return outs

    def window(self, deadline: float) -> dict:
        """Whole grids until the deadline has passed; a lane whose final
        parameters are not finite counts as failed."""
        import jax

        grids = failed = 0
        while True:
            outs = self._sweep()
            grids += 1
            failed += sum(not all(np.isfinite(np.asarray(l)).all()
                                  for l in jax.tree.leaves(p))
                          for cell in outs for p, _ in cell)
            if time.perf_counter() >= deadline:
                break
        lanes = len(self.cells) * self.n_rep
        return {"attempted": grids * lanes, "failed": failed,
                "amounts": {"cell_rounds_per_s": grids * lanes * self.T}}

    def facts(self, work: dict) -> dict:
        return {}

    def release(self):
        self.session = None

    # ------------------------------------------------------------ check

    # the control and the faults the comparison must catch, as reference
    # options (calibrate.py reads them over many seeds)
    CONTROL = {"precision": "high"}
    FAULTS = {"half_batch": {"fault": "half_batch"}}

    def program_readings(self):
        import jax

        return {"lanes": self.last,
                "params0": jax.tree.map(np.asarray, self.params0)}

    def check(self):
        return compare(self.program_readings(), self.reference(),
                       self.run.limits)

    def reference(self, precision: str = "highest", fault: str = ""):
        import jax

        cfg, grid = self.config, self.grid
        mc = cfg["mlmc"]
        setting = {"workers": self.m, "T": self.T, "j_cap": mc["j_cap"],
                   "V": mc["V"], "kappa": mc["kappa"],
                   "lr": cfg["optimizer"]["lr"]}
        lane = clf_ref.Lane(setting, self.X, self.y, precision=precision,
                            fault=fault)
        levels = clf_ref.level_plan(self.session_seed, lane.j_max, self.T)
        p0 = jax.tree.map(np.asarray, self.params0)
        out = {}
        for c, r in self.lanes_checked:
            attack, kw, K, rule, rkw = self.cells[c]
            seed = self.rep_seeds[r]
            masks = [clf_ref.periodic_mask(seed, self.m, int(grid["n_byz"]),
                                           K, t) for t in range(self.T)]
            params, oks = lane.run(p0, levels, self.sampler(seed), masks,
                                   (attack, kw), rule,
                                   float(next(iter(rkw.values()))))
            out[(c, r)] = (jax.tree.map(np.asarray, params), oks,
                           [int(j) for j in levels])
        return {"lanes": out, "params0": p0}


def leaf_gap(prog: dict, ref: dict) -> float:
    """Worst leaf's |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf (leaves the
    reference moves under a thousandth of the median leaf are left out);
    a lane that the reference leaves where it started (MFM with no median
    candidate sends zeros) must stay there."""
    med = float(np.median(list(ref.values())))
    if med == 0.0:
        return 0.0 if not any(prog.values()) else float("inf")
    return max(abs(prog[k] - r) / max(r, med) for k, r in ref.items()
               if r >= 1e-3 * med)


def change_norms(params, p0) -> dict:
    return {k: float(np.linalg.norm(np.asarray(params[k], np.float64)
                                    - np.asarray(p0[k], np.float64)))
            for k in p0}


def compare(prog: dict, ref: dict, limits: dict):
    gaps, mismatch = [], 0
    p0 = ref["params0"]
    for lane, (rp, roks, rlevels) in ref["lanes"].items():
        pp, oks, levels = prog["lanes"][lane]
        gaps.append(leaf_gap(change_norms(pp, p0), change_norms(rp, p0)))
        mismatch += sum(a != b for a, b in zip(oks + levels, roks + rlevels))
        mismatch += abs(len(oks + levels) - len(roks + rlevels))
    values = {"change_norm_gap": max(gaps), "log_mismatch": float(mismatch)}
    return [(k, v, float(limits[k])) for k, v in values.items()]
