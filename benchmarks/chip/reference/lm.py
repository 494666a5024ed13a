"""Plain reference of Mode B robust MLMC training of a Llama-style decoder.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
token embedding, per layer a pre-RMSNorm causal GQA attention with rotary
positions (rotate-half) and a pre-RMSNorm SwiGLU MLP, a final RMSNorm and
the tied output head, mean next-token cross-entropy. Per step: each
worker's gradient of its own mean loss at MLMC levels 0, J-1 and J (nested
row prefixes), the attack on the Byzantine workers, a coordinate-wise
trimmed mean over workers, the MLMC combine behind the fail-safe
(Algorithm 2, Eq. 6) and Adam. It imports nothing of the program.

``quant="fp8"`` is the control: every matrix product, forward and
backward, takes its two operands rounded to float8 e4m3 (per-tensor
scale), the step below the bfloat16 the configuration states.

Nothing here keeps a full batch of activations: each unit of rows is one
forward and backward, with every layer rematerialised, and the nested
level means are sums of unit gradients. Across several devices nothing
is gathered on one: each leaf's coordinates are cut into one block per
device (``Layout``), and a worker's whole replica is the only full-size
copy of a leaf a device holds beside that worker's own level sums.
"""
from __future__ import annotations

import math
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F8_MAX = 448.0  # largest finite float8 e4m3fn


def _q8(x):
    """Round to float8 e4m3 with a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _mm8(a, b):
    return jnp.matmul(_q8(a), _q8(b))


def _mm8_fwd(a, b):
    return _mm8(a, b), (a, b)


def _sum_to(x, shape):
    """Sum a cotangent over the axes its operand was broadcast along."""
    lead = x.ndim - len(shape)
    if lead:
        x = x.sum(tuple(range(lead)))
    axes = tuple(i for i, (a, b) in enumerate(zip(x.shape, shape))
                 if b == 1 and a != 1)
    return x.sum(axes, keepdims=True) if axes else x


def _mm8_bwd(res, g):
    a, b = res
    ga = jnp.matmul(_q8(g), _q8(jnp.swapaxes(b, -1, -2)))
    gb = jnp.matmul(_q8(jnp.swapaxes(a, -1, -2)), _q8(g))
    return _sum_to(ga, a.shape), _sum_to(gb, b.shape)


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def param_shapes(model: dict) -> dict:
    """'/'-joined parameter paths (the program's tree, layer-stacked) ->
    shapes."""
    L, D = model["num_hidden_layers"], model["hidden_size"]
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    hd, F, V = model["head_dim"], model["intermediate_size"], \
        model["vocab_size"]
    return {
        "embed": (V, D),
        "final_norm/scale": (D,),
        "blocks/b0/mix/ln/scale": (L, D),
        "blocks/b0/mix/wq": (L, D, H * hd),
        "blocks/b0/mix/wk": (L, D, KV * hd),
        "blocks/b0/mix/wv": (L, D, KV * hd),
        "blocks/b0/mix/wo": (L, H * hd, D),
        "blocks/b0/mlp/ln/scale": (L, D),
        "blocks/b0/mlp/dense/w1": (L, D, F),
        "blocks/b0/mlp/dense/w2": (L, F, D),
        "blocks/b0/mlp/dense/w3": (L, D, F),
    }


def stated_dtype(path: str, dtype: str):
    """The dtype a leaf is kept in: norm scales in float32, the rest in the
    configuration's dtype."""
    return jnp.float32 if path.endswith("scale") else jnp.dtype(dtype)


ROWS_PER_PASS = 4  # rows of one forward and backward: bounds activations


class Reference:
    def __init__(self, model: dict, *, quant: str = ""):
        self.model = model
        self.eps = float(model["rms_norm_eps"])
        self.mm = _mm8 if quant == "fp8" else jnp.matmul
        # runs on whichever device its committed arguments live on; the
        # running sum is donated, so it is updated in place
        self._accumulate = jax.jit(self._add_grad, donate_argnums=(0,))

    # ------------------------------------------------------------ model

    def _rms(self, x, scale):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + self.eps) * scale

    def _rope(self, x):
        """x: (B, S, heads, hd), rotate-half rotary positions."""
        hd = x.shape[-1]
        half = hd // 2
        freqs = 1.0 / (self.model["rope_theta"]
                       ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _layer(self, x, p):
        B, S, D = x.shape
        H, KV = self.model["num_attention_heads"], \
            self.model["num_key_value_heads"]
        hd, G = self.model["head_dim"], H // KV
        mm = self.mm
        h = self._rms(x, p["mix/ln/scale"])
        q = self._rope(mm(h, p["mix/wq"]).reshape(B, S, H, hd))
        k = self._rope(mm(h, p["mix/wk"]).reshape(B, S, KV, hd))
        v = mm(h, p["mix/wv"]).reshape(B, S, KV, hd)
        # query head i reads key/value head i // G
        q = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4)
        k = k.transpose(0, 2, 1, 3)[:, :, None]
        v = v.transpose(0, 2, 1, 3)[:, :, None]
        s = mm(q, jnp.swapaxes(k, -1, -2)) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm(a, v).transpose(0, 3, 1, 2, 4).reshape(B, S, H * hd)
        x = x + mm(o, p["mix/wo"])
        h = self._rms(x, p["mlp/ln/scale"])
        u = jax.nn.silu(mm(h, p["mlp/dense/w1"])) * mm(h, p["mlp/dense/w3"])
        return x + mm(u, p["mlp/dense/w2"])

    def _loss(self, params, tokens, labels):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens]
            stack = {k[len("blocks/b0/"):]: v for k, v in params.items()
                     if k.startswith("blocks/")}
            body = jax.checkpoint(lambda x, p: (self._layer(x, p), None))
            x, _ = lax.scan(body, x, stack)
            x = self._rms(x, params["final_norm/scale"])
            logits = self.mm(x, params["embed"].T)
            lse = jax.nn.logsumexp(logits, -1)
            gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
            return jnp.mean(lse - gold)

    def _add_grad(self, acc, params, ids, weight):
        loss, g = jax.value_and_grad(self._loss)(params, ids[:, :-1],
                                                 ids[:, 1:])
        return loss, jax.tree.map(lambda a, b: a + weight * b, acc, g)

    def add_unit(self, acc, params, ids):
        """``acc`` plus the gradient of the mean loss over one unit of rows
        ((rows, S + 1) ids), taken ``ROWS_PER_PASS`` rows at a time; returns
        (the passes' losses, still on the device, and the new sum). ``acc``
        is consumed. Nothing here waits for the device, so workers on
        different devices run side by side."""
        parts = max(ids.shape[0] // ROWS_PER_PASS, 1)
        rows = ids.shape[0] // parts
        losses = []
        for i in range(parts):
            lval, acc = self._accumulate(acc, params,
                                         ids[i * rows:(i + 1) * rows],
                                         jnp.float32(1.0 / parts))
            losses.append(lval)
        return losses, acc


def unit_loss(losses) -> float:
    """A unit's mean loss from the losses of its passes."""
    loss = 0.0
    for lval in losses:
        loss += float(lval) / len(losses)
    return loss


# ---------------------------------------------------------------- training


def trim_count(delta: float, m: int) -> int:
    """Rows trimmed at each end: ceil(delta * m) (delta as the decimal it
    was written as, in integer arithmetic), keeping at least one."""
    f = Fraction(delta).limit_denominator(10 ** 6)
    return min(-((-f.numerator * m) // f.denominator), (m - 1) // 2)


def trimmed_mean(stack, trim: int):
    """(m, ...) -> (...) coordinate-wise mean of the middle m - 2 * trim."""
    m = stack.shape[0]
    return jnp.sort(stack, axis=0)[trim:m - trim].mean(0)


def failsafe_coeff(mlmc: dict, m: int) -> float:
    """(1 + sqrt 2) c_E C V of Eq. 6, Option 1: c_E = sqrt(2 kappa + 1/m),
    C = sqrt(8 log(16 m^2 T))."""
    C = math.sqrt(8.0 * math.log(16.0 * m * m * mlmc["T"]))
    c_e = math.sqrt(2.0 * mlmc["kappa"] + 1.0 / m)
    return (1.0 + math.sqrt(2.0)) * c_e * C * mlmc["V"]


class Layout:
    """Where each leaf's coordinates live. With n devices, leaf k is cut
    along ``axis[k]``, its first axis that n divides, into n even blocks,
    block d on ``devices[d]``; a leaf that no axis divides, and every leaf
    on one device, is one block on the first device. A tree in this layout
    is {leaf: [blocks]}."""

    def __init__(self, shapes: dict, devices):
        self.devices, n = list(devices), len(devices)
        self.axis = {k: next((a for a, s in enumerate(sh) if s % n == 0),
                             None) if n > 1 else None
                     for k, sh in shapes.items()}

    def owners(self, k: str) -> list:
        return self.devices if self.axis[k] is not None else self.devices[:1]

    def shares(self, k: str, x) -> list:
        """``x``, a whole leaf k on any device, cut into its blocks where it
        is; the blocks are not moved."""
        if self.axis[k] is None:
            return [x]
        return jnp.split(x, len(self.devices), axis=self.axis[k])

    def scatter(self, tree: dict) -> dict:
        """Whole leaves -> blocks on their owners; each whole leaf is
        dropped once it is cut."""
        out = {}
        for k in list(tree):
            out[k] = [jax.device_put(b, d) for b, d in
                      zip(self.shares(k, tree.pop(k)), self.owners(k))]
        return out

    def whole(self, k: str, blocks: list, device):
        """Leaf k assembled from its blocks on ``device``."""
        if self.axis[k] is None:
            return jax.device_put(blocks[0], device)
        return jnp.concatenate([jax.device_put(b, device) for b in blocks],
                               self.axis[k])

    def host(self, k: str, blocks: list) -> np.ndarray:
        if self.axis[k] is None:
            return np.asarray(blocks[0])
        return np.concatenate([np.asarray(b) for b in blocks], self.axis[k])


def _sq(blocks: list):
    """A leaf's sum of squares: each device's partial sum in float32, added
    in float32 on the first block's device."""
    parts = [jnp.sum(jnp.square(b)) for b in blocks]
    dev = next(iter(parts[0].devices()))
    total = parts[0]
    for p in parts[1:]:
        total = total + jax.device_put(p, dev)
    return total


def _tree_norm(tree) -> float:
    return math.sqrt(sum(float(_sq(bl)) for bl in tree.values()))


def leaf_norms(tree) -> dict:
    return {k: float(jnp.sqrt(_sq(bl))) for k, bl in tree.items()}


def memory_in_use(devices) -> dict:
    """Bytes each device holds now, where the backend reports it (the
    caller waits for the work it wants counted)."""
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        if "bytes_in_use" in stats:
            out[str(d.id)] = int(stats["bytes_in_use"])
    return out


class Trainer:
    """Runs the reference through the first steps of a cell.

    ``train`` holds the traffic's training settings: ``workers`` m,
    ``global_batch`` rows per level unit, ``aggregator`` (cwtm),
    ``delta``, ``attack`` (sign_flip or none), ``mlmc`` {T, V, kappa,
    cap} and ``optimizer`` {kind: adam, lr, b1, b2, eps}. ``fault``
    plants one of the faults the comparison must catch:
    ``"half_batch"`` (each unit's gradient from its first half of rows),
    ``"state_unchanged"`` (the steps leave the parameters as they were) or
    ``"no_exchange"`` (the level sums never leave their worker's device:
    each block is aggregated over the workers on its own device alone).

    Worker w computes its gradients on ``devices[w % n]`` from a whole
    float32 replica. Every other value lives in blocks (``Layout``): the
    workers' level sums are cut into blocks, each sent to the device that
    owns it, and the attack, the trimmed mean, the MLMC difference and its
    norm, Adam's moments and the update run there, block by block; each
    step's replicas are assembled from the blocks. On one device the
    arithmetic is that of a single whole-leaf program. ``memory`` holds
    each device's largest ``bytes_in_use`` between the phases of a step.
    """

    def __init__(self, model: dict, train: dict, *, quant: str = "",
                 fault: str = "", devices=None):
        self.model, self.train, self.fault = model, train, fault
        self.m = int(train["workers"])
        self.devices = list(devices or jax.devices()[:1])
        self.ref = Reference(model, quant=quant)
        self.memory = {}
        opt = train["optimizer"]
        if train["aggregator"] != "cwtm" or opt["kind"] != "adam":
            raise ValueError("the reference runs cwtm and adam")
        self.lr, self.b1, self.b2, self.eps_adam = (
            float(opt["lr"]), float(opt["b1"]), float(opt["b2"]),
            float(opt["eps"]))

    def _note_memory(self):
        for d, b in memory_in_use(self.devices).items():
            self.memory[d] = max(self.memory.get(d, 0), b)

    def _worker_units(self, params, ids, w: int, units: int):
        """Worker w's sums of unit gradients over the nested prefixes of
        1, units/2 and units units, on its device from its replica
        ``params``, and its units' pass losses."""
        dev = self.devices[w % len(self.devices)]
        rows = ids.shape[0] // self.m  # this worker's local rows
        per_unit = rows // units
        local = ids[w * rows:(w + 1) * rows]
        acc = jax.tree.map(jnp.zeros_like, params)
        sums, losses = {}, []
        for u in range(units):
            block = jax.device_put(local[u * per_unit:(u + 1) * per_unit],
                                   dev)
            if self.fault == "half_batch":
                block = block[:max(per_unit // 2, 1)]
            lvals, acc = self.ref.add_unit(acc, params, block)
            losses.append(lvals)
            if u + 1 in (1, units // 2) and u + 1 < units:
                sums[u + 1] = jax.tree.map(jnp.copy, acc)
        sums[units] = acc
        return sums, losses

    def _aggregate(self, per_worker, n: int, mask, lay: Layout):
        """Level n, in blocks: each worker's level-n sum of a leaf is cut
        into blocks, each block sent to its owner, where the m values are
        divided by n, attacked and trimmed. Frees the workers' sums leaf by
        leaf as they leave."""
        trim = trim_count(float(self.train["delta"]), self.m)
        n_dev = len(self.devices)
        if self.train["attack"] not in ("sign_flip", "none"):
            raise ValueError(f"attack {self.train['attack']!r}")
        out = {}
        for k in list(per_worker[0][n]):
            shares = [lay.shares(k, pw[n].pop(k)) for pw in per_worker]
            blocks = []
            for d, dev in enumerate(lay.owners(k)):
                ws = list(range(self.m))
                if self.fault == "no_exchange":
                    here = [w for w in ws if w % n_dev == d]
                    ws = [here[i % len(here)] for i in range(self.m)]
                stack = jnp.stack([jax.device_put(shares[w][d], dev)
                                   for w in ws]) / n
                if self.train["attack"] == "sign_flip":
                    sign = jax.device_put(jnp.where(
                        jnp.asarray(np.asarray(mask)[ws]), -1.0, 1.0), dev)
                    stack = stack * sign.reshape((-1,) + (1,) *
                                                 (stack.ndim - 1))
                blocks.append(trimmed_mean(stack, trim))
            del shares
            out[k] = blocks
        return out

    def run(self, make_params0, step_ids, step_masks, step_levels):
        """Steps from the weights ``make_params0()`` gives (whole float32
        leaves on any device; called again at the end, rather than kept)
        through the given per-step (ids, mask, level). Returns
        {"loss": [...], "grad": leaf norms of the first step's gradient,
        "grad_full": that gradient on the host,
        "change": leaf norms of the parameters' change after the last
        step, "failsafe_ok" and "corr_norm" per MLMC step, "memory": each
        device's largest bytes in use}."""
        mlmc = self.train["mlmc"]
        cap, coeff = int(mlmc["cap"]), failsafe_coeff(mlmc, self.m)
        params = make_params0()
        lay = Layout({k: v.shape for k, v in params.items()}, self.devices)
        params = lay.scatter(params)
        mom = {k: [jnp.zeros_like(b) for b in bl] for k, bl in params.items()}
        vel = {k: [jnp.zeros_like(b) for b in bl] for k, bl in params.items()}
        out = {"loss": [], "failsafe_ok": [], "corr_norm": []}
        self.memory = {}
        for t, (ids, mask, J) in enumerate(zip(step_ids, step_masks,
                                               step_levels)):
            units = 2 ** J if 1 <= J <= cap else 1
            per_worker, losses, replicas = [], [], {}
            for w in range(self.m):
                dev = self.devices[w % len(self.devices)]
                if dev not in replicas:
                    replicas[dev] = {k: lay.whole(k, bl, dev)
                                     for k, bl in params.items()}
                sums, lw = self._worker_units(replicas[dev], ids, w, units)
                per_worker.append(sums)
                losses.append(lw)
            # the most that lives at once: a replica and a worker's level
            # sums on every device, beside the blocks
            jax.block_until_ready(per_worker)
            self._note_memory()
            del replicas
            agg = {n: self._aggregate(per_worker, n, mask, lay)
                   for n in sorted({1, max(units // 2, 1), units})}
            del per_worker
            g0 = agg[1]
            if 1 <= J <= cap:
                diff = {k: [a - b for a, b in zip(agg[units][k],
                                                  agg[units // 2][k])]
                        for k in g0}
                dn = _tree_norm(diff)
                ok = dn <= coeff / math.sqrt(2.0 ** J)
                g = {k: [a + (2.0 ** J if ok else 0.0) * b
                         for a, b in zip(g0[k], diff[k])] for k in g0}
                out["failsafe_ok"].append(bool(ok))
                out["corr_norm"].append(dn)
            else:
                g = g0
            del agg
            self._note_memory()
            out["loss"].append(float(np.mean([
                sum(unit_loss(u) for u in lw) / units for lw in losses])))
            if t == 0:
                out["grad"] = leaf_norms(g)
                out["grad_full"] = {k: lay.host(k, bl) for k, bl in g.items()}
            step = t + 1
            mom = {k: [self.b1 * a + (1 - self.b1) * b
                       for a, b in zip(mom[k], g[k])] for k in g}
            vel = {k: [self.b2 * a + (1 - self.b2) * b ** 2
                       for a, b in zip(vel[k], g[k])] for k in g}
            for k in params:
                if self.fault == "state_unchanged":
                    break
                upd = []
                for p, mk, vk in zip(params[k], mom[k], vel[k]):
                    mh = mk / (1 - self.b1 ** step)
                    vh = vk / (1 - self.b2 ** step)
                    upd.append(p - self.lr * mh / (jnp.sqrt(vh)
                                                   + self.eps_adam))
                params[k] = upd
        del mom, vel
        params0 = lay.scatter(make_params0())
        out["change"] = leaf_norms({k: [a - b for a, b in zip(params[k],
                                                              params0[k])]
                                    for k in params})
        out["memory"] = dict(self.memory)
        return out
