"""Mode B: robust MLMC training of a decoder LM, m workers on m chips.

The timed path is the program's ``launch/steps.py::build_mlmc_train_step``,
one compiled program per MLMC level, called exactly as
``launch/train.py`` calls it: the FSDP parameters and Adam state donated,
a token batch of (global batch x 2^J) rows, the Byzantine mask as data.
Set-up compiles the levels the traffic draws, makes the weights from the
seed on the device in the step's layout, and drives the first
``checked_steps`` steps through the same call and feed as the window;
their losses, the first gradient (Adam's first moment after one step) and
the parameters' change are what the reference is compared with.
"""
from __future__ import annotations

import math
import sys
import time

import numpy as np

from benchmarks.chip import arithmetic, generator
from benchmarks.chip.reference import lm


def _path(keys) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in keys)


def program_config(name: str, model: dict, dtype: str):
    """The program's ModelConfig for a Llama-style decoder file."""
    from repro.configs.base import ModelConfig

    if model["hidden_act"] != "silu" or not model["tie_word_embeddings"]:
        raise ValueError("the driver runs SwiGLU decoders with tied "
                         "embeddings")
    if model["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's RMSNorm has eps 1e-6")
    return ModelConfig(
        arch_id=name, family="dense", n_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        head_dim=model["head_dim"], rope_theta=float(model["rope_theta"]),
        tie_embeddings=True, norm="rmsnorm", act="swiglu", dtype=dtype)


class Driver:
    def __init__(self, run, devices):
        self.run, self.devices = run, devices
        self.model = run.config["model"]
        self.dtype = run.config["torch_dtype"]
        tr = run.traffic
        self.m, self.gb, self.seq = (int(tr["workers"]),
                                     int(tr["global_batch"]),
                                     int(tr["seq_len"]))
        self.checked = int(tr["checked_steps"])
        self.law = tr["levels"]
        self.cap = int(self.law["cap"])
        self.block = int(self.law["block"])
        self.readings = {}

    # ------------------------------------------------------------ inputs

    def level(self, t: int) -> int:
        return int(self._levels[t])

    def _levels_upto(self, n: int):
        self._levels = generator.levels(self.run.seed, self.law, n)

    def mask(self, t: int) -> np.ndarray:
        return generator.byzantine_mask(self.run.seed, self.run.traffic[
            "switcher"], self.m, t)

    def rows(self, J: int) -> int:
        return self.gb * 2 ** J

    # ------------------------------------------------------------ set-up

    def build(self):
        """Compile the levels the traffic draws and the helpers around
        them; nothing here depends on the seed."""
        import jax
        import jax.numpy as jnp

        from repro.configs.base import ShapeConfig
        from repro.core.mlmc import MLMCConfig
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_mlmc_train_step
        from repro.optim.optimizers import adam

        tr, gen = self.run.traffic, generator
        cfg = program_config(self.run.cell["config"], self.model, self.dtype)
        if len(self.devices) % self.m:
            raise ValueError(f"{self.m} workers on {len(self.devices)} chips")
        self.mesh = make_mesh((self.m, len(self.devices) // self.m),
                              ("data", "model"), devices=self.devices)
        shape = ShapeConfig("bench", self.seq, self.gb, "train")
        mc = tr["mlmc"]
        mlmc = MLMCConfig(T=int(mc["T"]), m=self.m, V=float(mc["V"]),
                          option=1, kappa=float(mc["kappa"]), j_cap=self.cap)
        if mlmc.j_max != self.cap:
            raise ValueError(f"MLMC j_max {mlmc.j_max} != cap {self.cap}")
        o = tr["optimizer"]
        self.b1 = float(o["b1"])
        opt = adam(float(o["lr"]), b1=self.b1, b2=float(o["b2"]),
                   eps=float(o["eps"]))
        levels = sorted(set(gen.level_block(self.law)))
        self.exe, self.make_batch = {}, {}
        shard = lambda like: jax.tree.map(lambda s: s.sharding, like)
        ref_shapes = lm.param_shapes(self.model)
        with jax.set_mesh(self.mesh):
            for J in levels:
                st = build_mlmc_train_step(
                    cfg, self.mesh, shape, mlmc, J,
                    aggregator=tr["aggregator"], attack=tr["attack"],
                    delta=float(tr["delta"]), opt=opt,
                    dtype=jnp.dtype(self.dtype))
                self.exe[J] = st.fn.lower(*st.inputs).compile()
                rows = self.rows(J)
                V = self.model["vocab_size"]
                self.make_batch[J] = jax.jit(
                    lambda key, t, rows=rows: gen.lm_batch(
                        gen.token_ids(key, t, rows, self.seq, V)),
                    out_shardings=shard(st.inputs[2]))
                self.mask_sharding = st.inputs[3].sharding
            flat, self.treedef = jax.tree_util.tree_flatten_with_path(
                st.inputs[0])
            self.paths = [_path(p) for p, _ in flat]
            got = {p: tuple(s.shape) for p, (_, s) in zip(self.paths, flat)}
            if got != {p: tuple(s) for p, s in ref_shapes.items()}:
                raise ValueError(f"the program's parameters {got} are not "
                                 f"the configuration's {ref_shapes}")
            specs = [(p, s.shape, s.dtype) for p, (_, s) in
                     zip(self.paths, flat)]

            def init(key):
                return self.treedef.unflatten(
                    [gen.weight(key, p, sh, dt) for p, sh, dt in specs])

            self.init = jax.jit(init, out_shardings=shard(st.inputs[0]))
            self.opt_init = jax.jit(opt.init,
                                    out_shardings=shard(st.inputs[1]))
            self._read_norms = jax.jit(lambda tree: [
                jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                for l in jax.tree.leaves(tree)])
            self._read_change = jax.jit(lambda p, key: [
                jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                            - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(p),
                                jax.tree.leaves(self.init(key)))])

    def prepare(self):
        """Weights and Adam state from the seed, then the checked steps
        through the window's own call and feed, read for the check."""
        import jax

        gen = generator
        with jax.set_mesh(self.mesh):
            self.wkey = gen.device_key(self.run.seed, gen.WEIGHTS)
            self.tkey = gen.device_key(self.run.seed, gen.TOKENS)
            self.params = self.init(self.wkey)
            self.opt_state = self.opt_init(self.params)
            self._levels_upto(self.checked + self.block)
            self.readings = {}
            losses, oks = [], []
            for t in range(self.checked):
                out = self._step(t)
                losses.append(float(out[0]))
                oks.append(bool(float(out[1]) > 0.5))
                if t == 0:  # Adam's first moment is (1 - b1) g
                    self.readings["grad"] = dict(zip(self.paths, [
                        float(v) / (1.0 - self.b1)
                        for v in self._read_norms(self.opt_state["m"])]))
                    self.readings["grad_full"] = dict(zip(self.paths, [
                        np.asarray(v, np.float32) / np.float32(1.0 - self.b1)
                        for v in jax.tree.leaves(self.opt_state["m"])]))
            self.readings["loss"] = losses
            self.readings["failsafe_ok"] = oks
            self.readings["change"] = dict(zip(self.paths, [
                float(v) for v in self._read_change(self.params, self.wkey)]))
            # on to a whole block of levels: the window starts and ends on
            # one, so that it holds each level in its exact share
            self.t = self.checked
            while self.t % self.block:
                out = self._step(self.t)
                self.t += 1
            jax.block_until_ready(out)

    def _step(self, t: int):
        import jax

        J = self.level(t)
        with self.run.span("place"):
            batch = self.make_batch[J](self.tkey, t)
            maskf = jax.device_put(self.mask(t).astype(np.float32),
                                   self.mask_sharding)
        with self.run.span("dispatch"):
            self.params, self.opt_state, out = self.exe[J](
                self.params, self.opt_state, batch, maskf)
        return out

    # ------------------------------------------------------------ window

    def window(self, deadline: float) -> dict:
        """Whole blocks of levels until the deadline has passed, one step
        in flight; every step dispatched is waited for and counted."""
        import jax

        self._levels_upto(self.t + 100_000)
        tokens = steps = failed = 0
        done = []

        def finish(out, J):
            nonlocal tokens, steps, failed
            with self.run.span("wait"):
                loss = float(out[0])
            steps += 1
            failed += not math.isfinite(loss)
            tokens += self.rows(J) * self.seq
            done.append(J)

        prev = None
        with jax.set_mesh(self.mesh):
            while True:
                out = self._step(self.t)
                if prev is not None:
                    finish(*prev)
                prev = (out, self.level(self.t))
                self.t += 1
                if (time.perf_counter() >= deadline
                        and self.t % self.block == 0):
                    break
            finish(*prev)
        return {"attempted": steps, "failed": failed,
                "amounts": {"tokens_per_s": tokens}, "levels": done}

    def facts(self, work: dict) -> dict:
        """Required work of the window's steps, counted from shapes."""
        a = arithmetic
        tokens = work["amounts"]["tokens_per_s"]
        n_params = a.lm_param_count(self.model)
        dtype_bytes = int(np.dtype(lm.stated_dtype("w", self.dtype)).itemsize)
        return {
            "train_flops": tokens * a.lm_train_flops_per_token(self.model,
                                                               self.seq),
            "agg_bytes_per_chip": sum(
                a.mlmc_aggregations(J, self.cap) for J in work["levels"])
            * a.aggregation_bytes(n_params, self.m, len(self.devices),
                                  dtype_bytes),
        }

    # ------------------------------------------------------------ check

    def release(self):
        """Drop the program's state (the compiled levels stay)."""
        self.params = self.opt_state = None

    # the control and the faults the comparison must catch, as reference
    # options (calibrate.py reads them over many seeds)
    CONTROL = {"quant": "fp8"}

    @property
    def FAULTS(self) -> dict:
        """Half of each unit's rows, the state unchanged and, where there
        are workers to exchange with, the exchange left out."""
        faults = {"half_batch": {"fault": "half_batch"},
                  "state_unchanged": {"fault": "state_unchanged"}}
        if self.m > 1:
            faults["no_exchange"] = {"fault": "no_exchange"}
        return faults

    def program_readings(self):
        return self.readings

    def check(self):
        """The reference through the checked steps, compared with the
        program's readings: [(name, value, limit)]."""
        ref = self.reference()
        print(f"[{self.run.name}] reference bytes in use at its high point, "
              f"per device: {ref['memory']}", file=sys.stderr, flush=True)
        return compare(self.readings, ref, self.run.limits)

    def reference(self, **kw):
        import jax
        import jax.numpy as jnp

        tr, gen = self.run.traffic, generator
        train = dict(tr, mlmc=dict(tr["mlmc"], cap=self.cap))
        trainer = lm.Trainer(self.model, train, devices=self.devices, **kw)
        dev = self.devices[0]
        shapes = lm.param_shapes(self.model)
        wkey = jax.device_put(gen.device_key(self.run.seed, gen.WEIGHTS), dev)

        def params0():  # the configuration's bf16 values, in float32
            return {p: gen.weight(wkey, p, s, lm.stated_dtype(p, self.dtype))
                    .astype(jnp.float32) for p, s in shapes.items()}

        tkey = jax.device_put(gen.device_key(self.run.seed, gen.TOKENS), dev)
        levels = [int(j) for j in gen.levels(self.run.seed, self.law,
                                             self.checked)]
        ids = [gen.token_ids(tkey, t, self.rows(J), self.seq,
                             self.model["vocab_size"])
               for t, J in enumerate(levels)]
        masks = [self.mask(t) for t in range(self.checked)]
        return trainer.run(params0, ids, masks, levels)


def moved(ref_grad: dict) -> set:
    """Leaves the reference's first gradient moves: a leaf whose gradient
    norm is under a thousandth of the median leaf's moves under Adam by
    round-off alone, and is left out."""
    med = float(np.median(list(ref_grad.values())))
    return {k for k, r in ref_grad.items() if r >= 1e-3 * med}


def leaf_gaps(prog: dict, ref: dict, keep: set) -> list:
    """Per kept leaf, |program norm - reference norm| over the larger of
    the reference's norm of that leaf and of the median leaf."""
    med = float(np.median(list(ref.values())))
    return [abs(prog[k] - ref[k]) / max(ref[k], med) for k in sorted(keep)]


def grad_diff(prog: dict, ref: dict, ref_norms: dict, keep: set) -> float:
    """Worst kept leaf's norm of (program gradient - reference gradient)
    over the larger of the reference's norm of that leaf and of the median
    leaf. A gap of norms moves with rounding only at second order, so the
    control one precision step down barely moves it; this moves at first
    order (PERF.md)."""
    med = float(np.median(list(ref_norms.values())))
    return max(float(np.linalg.norm(prog[k] - ref[k])) / max(ref_norms[k],
                                                              med)
               for k in sorted(keep))


def compare(prog: dict, ref: dict, limits: dict):
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    failsafe = sum(a != b for a, b in zip(prog["failsafe_ok"],
                                          ref["failsafe_ok"]))
    keep = moved(ref["grad"])
    values = {"loss_gap": loss,
              "grad_norm_gap": max(leaf_gaps(prog["grad"], ref["grad"],
                                             keep)),
              "grad_diff": grad_diff(prog["grad_full"], ref["grad_full"],
                                     ref["grad"], keep),
              "change_norm_gap": max(leaf_gaps(prog["change"], ref["change"],
                                               keep)),
              "failsafe_mismatch": float(failsafe)}
    return [(k, v, float(limits[k])) for k, v in values.items()]
