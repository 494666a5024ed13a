"""``correct`` must come out false for the control and for every fault a
cell can have, at a size a CPU test run holds.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q \\
        benchmarks/chip/tests/test_correct.py

Each test drives the harness (``run.execute``) past its look for a chip,
at the cell's own traffic and limits (``checks/<cell>.json``) but a
configuration cut down in size, and breaks the timed path underneath: a
step that returns its state unchanged, half of each batch left out with
the mean taken over the rest, or (the grid) every answer altered where it
is returned. The control is the reference one precision step down put in
the program's place.
"""
import copy
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import run as bench_run  # noqa: E402

LM = "smollm-360m.mlmc.1chip"
GRID = "clf-m17.grid1024"


def small_run(workload: str, seed: int = 2 ** 31 + 3) -> bench_run.Run:
    bench = bench_run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    run = bench_run.Run.find(bench, workload, seed, 1.0, False)
    run.config = copy.deepcopy(run.config)
    if workload == LM:
        run.config["model"].update(
            num_hidden_layers=2, hidden_size=256, intermediate_size=512,
            num_attention_heads=4, num_key_value_heads=2, head_dim=64,
            vocab_size=512)
        run.traffic = dict(run.traffic, seq_len=64)
    else:
        run.traffic = dict(run.traffic, thetas=[0.2], Ks=[5, 10],
                           replicates=2, lane_chunk=8)
        run.config["mlmc"] = dict(run.config["mlmc"], T=24)
    return run


def execute(run, patch=None):
    return bench_run.execute(run, chip_check=False, patch=patch)


def failed_checks(result):
    return [n for n, c in result["checks"].items()
            if not c["value"] <= c["limit"]]


# ---------------------------------------------------------------- LM


def _lm_unchanged(driver):
    import jax.numpy as jnp

    for J, real in list(driver.exe.items()):
        def step(p, o, b, m, real=real):
            out = real(jax.tree.map(jnp.copy, p), jax.tree.map(jnp.copy, o),
                       b, m)[2]
            return p, o, out
        driver.exe[J] = step


def _lm_half_batch(driver):
    """Each level unit's rows: the first half, twice over."""
    import jax.numpy as jnp

    rows = driver.gb // driver.m

    def halve(x):
        u = x.reshape(-1, rows, x.shape[-1])[:, :rows // 2]
        return jnp.concatenate([u, u], 1).reshape(x.shape)

    for J, real in list(driver.make_batch.items()):
        driver.make_batch[J] = (lambda key, t, real=real:
                                {k: halve(v) for k, v in real(key, t).items()})


@pytest.mark.parametrize("fault", [_lm_unchanged, _lm_half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_lm_fault_is_not_correct(fault):
    result = execute(small_run(LM), patch=fault)
    assert result["correct"] is False, result["checks"]
    assert failed_checks(result)


def test_lm_control_is_not_correct():
    run = small_run(LM)
    bench_run.start(run, chip_check=False)
    driver = bench_run.make_driver(run, jax.devices()[:1])
    driver.build()
    from benchmarks.chip.drivers import lm_mlmc

    checks = lm_mlmc.compare(driver.reference(**driver.CONTROL),
                             driver.reference(), run.limits)
    assert any(not v <= lim for _, v, lim in checks), checks


def test_lm_intact_is_correct():
    result = execute(small_run(LM))
    assert result["correct"] is True, result["checks"]


# ---------------------------------------------------------------- grid


def _grid_unchanged(driver):
    make = driver._session

    def session(params0, rep_seeds):
        s = make(params0, rep_seeds)
        sweep = s.sweep

        def frozen(spec, T, **kw):
            return [[(params0, logs) for _, logs in cell]
                    for cell in sweep(spec, T, **kw)]
        s.sweep = frozen
        return s

    driver._session = session


def _grid_half_batch(driver):
    import jax.numpy as jnp

    real = driver.feed

    def feed(seed):
        draw = real(seed)

        def halved(t, n):
            idx = draw(t, n)
            h = idx[..., :idx.shape[-1] // 2]
            return jnp.concatenate([h, h], -1)
        return halved

    driver.feed = feed


def _grid_answer_altered(driver):
    """Every lane's final parameters altered by one part in 10^4 where the
    sweep returns them."""
    make = driver._session

    def session(params0, rep_seeds):
        s = make(params0, rep_seeds)
        sweep = s.sweep

        def altered(spec, T, **kw):
            return [[(jax.tree.map(lambda x: x * (1 + 1e-4), p), logs)
                     for p, logs in cell] for cell in sweep(spec, T, **kw)]
        s.sweep = altered
        return s

    driver._session = session


@pytest.mark.parametrize("fault", [_grid_unchanged, _grid_half_batch,
                                   _grid_answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_grid_fault_is_not_correct(fault):
    result = execute(small_run(GRID), patch=fault)
    assert result["correct"] is False, result["checks"]


def test_grid_control_separates():
    """The control at the cell's own 150 rounds reads at least three times
    the program's gap. Its ``correct`` false is shown on the chip only: at
    this cut size on the CPU it reads about 2e-7, under the limit that
    readings on the chip at the cell's size set (PERF.md section 6)."""
    run = small_run(GRID)
    run.traffic = dict(run.traffic, Ks=[5])
    run.config["mlmc"]["T"] = 150
    bench_run.start(run, chip_check=False)
    driver = bench_run.make_driver(run, jax.devices()[:1])
    driver.build()
    run.seed = 5
    driver.prepare()
    from benchmarks.chip.drivers import clf_sweep

    ref = driver.reference()
    prog = dict((n, v) for n, v, _ in clf_sweep.compare(
        driver.program_readings(), ref, run.limits))
    ctl = dict((n, v) for n, v, _ in clf_sweep.compare(
        driver.reference(**driver.CONTROL), ref, run.limits))
    assert ctl["change_norm_gap"] >= 3 * prog["change_norm_gap"], (prog, ctl)


def test_grid_intact_is_correct():
    result = execute(small_run(GRID))
    assert result["correct"] is True, result["checks"]


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
