"""Plain reference of the paper's Algorithm 2 on the classification testbed,
one scenario lane at a time.

A lane is one (attack, Periodic(K) switcher, rule and hyperparameter,
replicate seed) of the grid. Per round t: the MLMC level J (J ~ Geom(1/2),
beyond the cap J_max the correction is dropped), each of the m workers'
gradients of the 64-128-10 tanh MLP's mean cross-entropy on each of its
n = 2^J units of rows, the attack on that round's Byzantine workers unit
by unit, the rule on the workers' level-0, level-(J-1) and level-J means,
the combine g = g^0 + 2^J (g^J - g^(J-1)) when ||g^J - g^(J-1)|| stays
under the fail-safe bound (Eq. 6) and g^0 alone otherwise, and SGD.
Straightforward ``jax.numpy``, float32 at ``highest`` matmul precision;
it imports nothing of the program.

The level plan and the switcher's masks follow the laws the scenario
names: J from ``numpy.random.default_rng(session seed)``, one geometric
draw per round; the Byzantine set of epoch e = t // K from
``default_rng(replicate seed * 1_000_003 + e)``.

``precision="high"`` is the control: every matrix product in three
bfloat16 passes (hi*hi + hi*lo + lo*hi), the step below the float32 at
``highest`` the configuration states. ``fault="half_batch"`` plants a
fault the comparison must catch: each unit's gradient from half its rows.
"""
from __future__ import annotations

import functools
import math
from fractions import Fraction

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def matmul_high(a, b):
    """A float32 product in three bfloat16 passes."""
    ah, al = _split(a)
    bh, bl = _split(b)
    hp = jax.lax.Precision.HIGHEST
    return (jnp.matmul(ah, bh, precision=hp) + jnp.matmul(ah, bl, precision=hp)
            + jnp.matmul(al, bh, precision=hp))


def level_plan(session_seed: int, j_max: int, T: int) -> np.ndarray:
    rng = np.random.default_rng(session_seed)
    return np.array([min(int(rng.geometric(0.5)), j_max + 1)
                     for _ in range(T)], np.int32)


def periodic_mask(seed: int, m: int, n_byz: int, K: int, t: int):
    rng = np.random.default_rng(seed * 1_000_003 + t // K)
    mask = np.zeros(m, bool)
    mask[rng.choice(m, n_byz, replace=False)] = True
    return mask


def count_ceil(fraction: float, m: int) -> int:
    """ceil(fraction * m), exactly: the fraction as the decimal it was
    written as (0.3, not 0.299999...), then integer arithmetic."""
    f = Fraction(fraction).limit_denominator(10 ** 6)
    return -((-f.numerator * m) // f.denominator)


def failsafe_coeff(m: int, T: int, V: float, kappa: float, option: int):
    C = math.sqrt(8.0 * math.log(16.0 * m * m * T))
    c_e = 6.0 * math.sqrt(2.0) if option == 2 else math.sqrt(2.0 * kappa
                                                             + 1.0 / m)
    return (1.0 + math.sqrt(2.0)) * c_e * C * V


class Lane:
    """Runs one lane. ``setting``: workers m, unit_batch, T, j_cap, V,
    kappa, lr, n_byz; ``data``: (X, y) training arrays on the device."""

    def __init__(self, setting: dict, X, y, *, precision: str = "highest",
                 fault: str = ""):
        self.s, self.X, self.y, self.fault = setting, X, y, fault
        self.mm = (matmul_high if precision == "high" else functools.partial(
            jnp.matmul, precision=jax.lax.Precision.HIGHEST))
        self.m = int(setting["workers"])
        self.j_max = min(int(math.log2(max(setting["T"], 2))),
                         int(setting["j_cap"]))
        # the attack and the rule's hyperparameter are data, so that one
        # program per (level, rule) serves every lane and seed
        self._round = jax.jit(self._round_body,
                              static_argnames=("J", "rule", "n"))

    def _loss(self, p, idx):
        if self.fault == "half_batch":  # the mean over half of each unit
            idx = idx[:idx.shape[0] // 2]
        x, y = self.X[idx], self.y[idx]
        h = jnp.tanh(self.mm(x, p["w1"]) + p["b1"])
        logits = self.mm(h, p["w2"]) + p["b2"]
        lse = jax.nn.logsumexp(logits, -1)
        return jnp.mean(lse - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])

    # ------------------------------------------------------------ attacks

    ATTACKS = ("none", "sign_flip", "ipm", "alie")

    def _attack(self, g, mask, attack):
        """g: (m, d) one unit's worker gradients, flattened; ``attack`` =
        (index into ATTACKS, scale, eps, z); the mean and variance are the
        honest workers' (the omniscient attacker)."""
        which, scale, eps, z = attack
        w = (~mask).astype(jnp.float32)
        w = w / jnp.maximum(w.sum(), 1.0)
        mu = w @ g
        var = w @ (g - mu) ** 2
        bad = jnp.stack([g, -scale * g,
                         jnp.broadcast_to(-eps * mu, g.shape),
                         jnp.broadcast_to(mu - z * jnp.sqrt(var + 1e-12),
                                          g.shape)])[which]
        return jnp.where(mask[:, None], bad, g)

    @classmethod
    def attack_data(cls, name: str, kw: dict):
        if name not in cls.ATTACKS:
            raise ValueError(f"attack {name!r}")
        return (jnp.int32(cls.ATTACKS.index(name)),
                jnp.float32(kw.get("scale", 1.0)),
                jnp.float32(kw.get("eps", 0.1)), jnp.float32(kw.get("z", 1.22)))

    # ------------------------------------------------------------ rules

    def rule_data(self, name: str, theta: float):
        """The rule's hyperparameter as the rule reads it: rows trimmed at
        each end (cwtm), rows scored (krum), the radius tau (mfm)."""
        m = self.m
        if name == "cwtm":
            return jnp.float32(min(max(count_ceil(theta, m), 0), (m - 1) // 2))
        if name == "krum":
            return jnp.float32(max(m - count_ceil(theta, m) - 2, 1))
        return jnp.float32(theta)

    def _rule(self, g, name: str, k):
        """g: (m, d) -> (d,); ``k`` from ``rule_data``."""
        m = self.m
        rows = jnp.arange(m, dtype=jnp.float32)
        if name == "cwmed":
            return jnp.median(g, axis=0)
        if name == "cwtm":  # the mean of the sorted rows k .. m - k - 1
            keep = ((rows >= k) & (rows < m - k))[:, None]
            return (jnp.where(keep, jnp.sort(g, axis=0), 0.0).sum(0)
                    / keep.sum())
        d2 = jnp.sum((g[:, None, :] - g[None, :, :]) ** 2, -1)
        if name == "krum":  # the row whose k nearest others lie closest
            d2 = d2 + jnp.diag(jnp.full((m,), jnp.inf))
            scores = jnp.where(rows < k, jnp.sort(d2, axis=1), 0.0).sum(1)
            return g[jnp.argmin(scores)]
        if name == "mfm":
            tau = k
            d = jnp.sqrt(d2)
            cand = (d <= tau / 2).sum(1) > m / 2
            close = d[jnp.argmax(cand)] <= tau
            w = jnp.where(cand.any(), close / jnp.maximum(close.sum(), 1), 0.0)
            return w @ g
        raise ValueError(f"rule {name!r}")

    # ------------------------------------------------------------ rounds

    def _round_body(self, flat_p, idx, mask, coeff, attack, k, *, J: int,
                    rule, n: int):
        unravel = self._unravel
        grads = jax.vmap(jax.vmap(lambda i: jax.flatten_util.ravel_pytree(
            jax.grad(self._loss)(unravel(flat_p), i))[0]))(idx)  # (m, n, d)
        mask = mask.astype(bool)
        grads = jnp.stack([self._attack(grads[:, u], mask, attack)
                           for u in range(n)], 1)
        g0 = self._rule(grads[:, 0], rule, k)
        if 1 <= J <= self.j_max:
            gh = self._rule(grads[:, :n // 2].mean(1), rule, k)
            gj = self._rule(grads.mean(1), rule, k)
            diff = gj - gh
            dn = jnp.sqrt(jnp.sum(diff ** 2))
            ok = dn <= coeff / math.sqrt(2.0 ** J)
            g = g0 + jnp.where(ok, 2.0 ** J, 0.0) * diff
        else:
            g, ok = g0, jnp.array(True)
        return flat_p - self.s["lr"] * g, ok

    def run(self, params0: dict, levels, sampler, masks, attack, rule,
            theta: float):
        """The lane's final parameters and per-round fail-safe verdicts.
        ``sampler(t, n)`` -> (m, n, unit_batch) indices; ``masks[t]`` (m,)."""
        flat, self._unravel = jax.flatten_util.ravel_pytree(params0)
        coeff = failsafe_coeff(self.m, int(self.s["T"]), float(self.s["V"]),
                               float(self.s["kappa"]),
                               2 if rule == "mfm" else 1)
        atk = self.attack_data(*attack)
        k = self.rule_data(rule, float(theta))
        oks = []
        for t, J in enumerate(levels):
            n = 2 ** int(J) if 1 <= J <= self.j_max else 1
            flat, ok = self._round(flat, sampler(t, n), jnp.asarray(masks[t]),
                                   coeff, atk, k, J=int(J), rule=rule, n=n)
            oks.append(bool(ok))
        return self._unravel(flat), oks
