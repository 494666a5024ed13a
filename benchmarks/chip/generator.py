"""The one generator that every traffic file is read by.

Every input of a run is a pure function of ``--seed``: host-side laws
(MLMC levels, Byzantine masks, the scenario grid) draw from
``host_rng(seed, stream)``, device-side tensors (tokens, weights, data)
from ``device_key(seed, stream)``. Streams keep the draws independent, so
adding one never moves another.

These are the benchmark's own copies of the laws the program also has
(``core/mlmc.py::sample_level``, ``core/switching.py::Periodic``,
``launch/train.py``'s level cap, ``benchmarks/bench_scan_driver.py``'s
1024-lane grid): a later change to the program cannot move them.
"""
from __future__ import annotations

import zlib

import numpy as np

# stream ids: one per kind of draw
LEVELS, MASKS, TOKENS, WEIGHTS, DATA, SCENARIO = range(1, 7)
MAX_UNITS = 64  # within-round units a worker may draw (2^6)


def host_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, *stream])


def device_key(seed: int, *stream: int):
    """A JAX PRNG key carrying 62 bits of (seed, stream)."""
    import jax

    words = np.random.SeedSequence([int(seed) % 2 ** 64, *stream]) \
        .generate_state(2, np.uint32)
    key = jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF)
    return jax.random.fold_in(key, int(words[1]) & 0x7FFFFFFF)


# ---------------------------------------------------------------- levels


def level_block(law: dict) -> list:
    """The levels one balanced block holds. ``{"law": "geometric", "p":
    0.5, "cap": 2}`` is J ~ Geom(p) on {1, 2, ...} with every J > cap set
    to cap (``launch/train.py``'s cap): P(J=1) = 1/2, P(J=2) = 1/2, so a
    block of two holds one of each. ``block`` levels are drawn so that the
    block holds each level in its exact share."""
    if law["law"] != "geometric":
        raise ValueError(f"unknown level law {law['law']!r}")
    p, cap, block = float(law["p"]), int(law["cap"]), int(law["block"])
    probs = [p * (1 - p) ** (j - 1) for j in range(1, cap)]
    probs.append(1.0 - sum(probs))  # the capped tail
    counts = [round(q * block) for q in probs]
    if sum(counts) != block or any(abs(c - q * block) > 1e-9
                                   for c, q in zip(counts, probs)):
        raise ValueError(f"a block of {block} cannot hold the shares {probs}")
    return [j for j, c in zip(range(1, cap + 1), counts) for _ in range(c)]


def levels(seed: int, law: dict, n: int) -> np.ndarray:
    """(n,) MLMC levels: balanced blocks, each in its own seeded order, so
    every seed runs the same mix of levels and only their order moves."""
    block = level_block(law)
    rng = host_rng(seed, LEVELS)
    out = []
    while len(out) < n:
        out.extend(rng.permutation(block).tolist())
    return np.asarray(out[:n], np.int32)


# ---------------------------------------------------------------- masks


def byzantine_mask(seed: int, switcher: dict, m: int, t: int) -> np.ndarray:
    """(m,) bool: who is Byzantine in round ``t``. ``static`` keeps one
    seeded set; ``periodic`` draws a fresh set of ``n_byz`` every ``K``
    rounds (the paper's Periodic(K))."""
    kind, n_byz = switcher["kind"], int(switcher["n_byz"])
    epoch = 0 if kind == "static" else t // int(switcher["K"])
    if kind not in ("static", "periodic"):
        raise ValueError(f"unknown switcher {kind!r}")
    mask = np.zeros(m, bool)
    mask[host_rng(seed, MASKS, epoch).choice(m, n_byz, replace=False)] = True
    return mask


# ---------------------------------------------------------------- tokens


def token_ids(key, t, rows: int, seq_len: int, vocab: int):
    """(rows, seq_len + 1) int32 token ids, uniform over the vocabulary,
    from ``key = device_key(seed, TOKENS)``; step ``t``'s rows all differ
    from every other step's. Traceable in ``key`` and ``t``."""
    import jax
    import jax.numpy as jnp

    return jax.random.randint(jax.random.fold_in(key, t),
                              (rows, seq_len + 1), 0, vocab, jnp.int32)


def lm_batch(ids):
    """Next-token pairs from (rows, S + 1) ids."""
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


# ---------------------------------------------------------------- weights


def weight(key, path: str, shape, dtype):
    """One parameter leaf, named by its '/'-joined path, from ``key =
    device_key(seed, WEIGHTS)``. Norm scales are ones, the embedding
    N(0, 0.02^2), every other matrix N(0, 1/fan_in) with fan_in the
    second-to-last axis. Drawn in float32, then cast. Traceable in key."""
    import jax
    import jax.numpy as jnp

    if path.endswith("scale"):
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    std = 0.02 if path == "embed" else 1.0 / np.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def mlp_weights(key, sizes) -> dict:
    """``{"w1", "b1", "w2", "b2", ...}`` of an MLP with layer ``sizes``:
    weights N(0, 1/fan_in), biases zero, float32. Traceable in key."""
    import jax
    import jax.numpy as jnp

    out = {}
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:]), start=1):
        k = jax.random.fold_in(key, i)
        out[f"w{i}"] = jax.random.normal(k, (a, b), jnp.float32) / np.sqrt(a)
        out[f"b{i}"] = jnp.zeros((b,), jnp.float32)
    return out


# ---------------------------------------------------------------- grid


def grid_cells(grid: dict) -> list:
    """The scenario grid, rule-major so that every ``lane_chunk`` of cells
    holds one rule: ``[(attack, attack_kwargs, K, rule, rule_kwargs)]``."""
    cells = []
    for rule in grid["rules"]:
        for theta in grid["thetas"]:
            for attack in grid["attacks"]:
                for K in grid["Ks"]:
                    cells.append((attack["name"], dict(attack.get("kw", {})),
                                  int(K), rule["name"],
                                  {rule["theta"]: float(theta)}))
    return cells


def replicate_seeds(seed: int, n: int) -> tuple:
    """``n`` replicate seeds for the grid's lanes (masks, batches)."""
    base = int(host_rng(seed, SCENARIO).integers(0, 2 ** 20))
    return tuple(base * n + r for r in range(n))


def mixture_dataset(seed: int, data: dict):
    """The Gaussian-mixture classification set (``data/pipeline.py``'s
    law: class means on a sphere of radius ``radius``, isotropic noise),
    drawn from the seed: (X_train, y_train, X_test, y_test), float32/int32."""
    rng = host_rng(seed, DATA)
    k, dim = int(data["classes"]), int(data["dim"])
    n = int(data["n_train"]) + int(data["n_test"])
    means = rng.normal(size=(k, dim))
    means *= float(data["radius"]) / np.linalg.norm(means, axis=1,
                                                    keepdims=True)
    y = rng.integers(0, k, size=n)
    X = means[y] + float(data["noise"]) * rng.normal(size=(n, dim))
    X, y = X.astype(np.float32), y.astype(np.int32)
    ntr = int(data["n_train"])
    return X[:ntr], y[:ntr], X[ntr:], y[ntr:]


def unit_indices(key, t, m: int, k: int, unit_batch: int, n_train: int):
    """(m, k, unit_batch) training indices of round ``t`` from ``key =
    device_key(replicate seed, DATA)``: worker w's within-round unit j is
    the same rows whatever k is (the MLMC nesting). Traceable in ``t``."""
    import jax
    import jax.numpy as jnp

    if k > MAX_UNITS:
        raise ValueError(f"{k} units per round, the law holds {MAX_UNITS}")
    key = jax.random.fold_in(key, t)
    full = jax.random.randint(key, (m, unit_batch * MAX_UNITS), 0, n_train,
                              jnp.int32)
    return full[:, :k * unit_batch].reshape(m, k, unit_batch)
