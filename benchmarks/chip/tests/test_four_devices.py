"""The four-chip Mode B cell on four CPU devices, at a size a test run
holds.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q \\
        benchmarks/chip/tests/test_four_devices.py

The cases run in one child process with four host devices
(``--xla_force_host_platform_device_count=4``), so that this process
keeps its one device; the child prints one JSON object of readings. The
reference on four devices, each leaf in blocks on its own device, must
follow the reference on one device within float32 rounding; the harness
must find the intact program correct, and not correct where the timed
path is broken underneath: the worker exchange left out, half of each
unit's rows, the state unchanged; the control (fp8 matrix products) must
fail a check too.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
FOUR = "smollm-360m.mlmc.4chip-m4"
TRAFFIC = "mlmc.4chip-m4"
PARITY_RTOL = 1e-6  # float32 rounding: the norms add blocks in a new order
CASES = ("intact", "no_exchange", "half_batch", "state_unchanged")


def _lm_no_exchange(driver):
    """The worker all-to-all left out: every device keeps its own worker's
    values of its own parameter block, in place of all m workers', so CWTM
    aggregates that one gradient."""
    import jax.numpy as jnp
    from jax import lax

    from repro.core import sharded

    def own_block(g, cfg, axis):
        blk = g.shape[axis] // cfg.m
        idx = lax.axis_index(cfg.axis_names)
        own = lax.dynamic_slice_in_dim(g, idx * blk, blk, axis)
        return jnp.broadcast_to(own[None], (cfg.m,) + own.shape)

    real = sharded._exchange_worker_blocks
    sharded._exchange_worker_blocks = own_block
    try:
        driver.build()
    finally:
        sharded._exchange_worker_blocks = real


def four_run():
    """The 1-chip cell's cut configuration and limits under the four-chip
    traffic (``traffic/mlmc.4chip-m4.json``), sequences cut as the other
    CPU tests cut them. The four-chip cell is not in ``BENCHMARK.json``
    yet (PERF.md section 7)."""
    import test_correct as tc

    from benchmarks.chip import run as bench_run

    run = tc.small_run(tc.LM)
    traffic = bench_run.read_json(f"traffic/{TRAFFIC}.json")
    run.cell = dict(run.cell, name=FOUR, traffic=TRAFFIC, chips=4)
    run.name, run.chips = FOUR, 4
    run.traffic = dict(traffic, seq_len=run.traffic["seq_len"])
    return run


def _relative(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def child() -> dict:
    """Every case, in this process of four devices."""
    sys.path.insert(0, HERE)
    import jax

    # two programs of several CPU devices in flight at once can deadlock in
    # their collectives' rendezvous; a chip runs each device's programs in
    # the order they were dispatched
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    jax.config.update("jax_enable_compilation_cache", False)
    import test_correct as tc

    from benchmarks.chip import run as bench_run
    from benchmarks.chip.drivers import lm_mlmc

    devs = jax.devices()
    assert len(devs) == 4, devs
    out = {}
    patches = {"intact": None, "no_exchange": _lm_no_exchange,
               "half_batch": tc._lm_half_batch,
               "state_unchanged": tc._lm_unchanged}
    for case, patch in patches.items():
        result = tc.execute(four_run(), patch=patch)
        out[case] = {"correct": result["correct"],
                     "checks": result["checks"]}

    run = four_run()
    driver = bench_run.make_driver(run, devs)
    driver.build()
    driver.devices = devs[:1]
    one = driver.reference()
    driver.devices = devs
    four = driver.reference()
    out["parity"] = {
        "loss": _relative(four["loss"], one["loss"]),
        "grad": max(_relative(four["grad"][k], one["grad"][k])
                    for k in one["grad"]),
        "grad_full": max(_relative(four["grad_full"][k], one["grad_full"][k])
                         for k in one["grad_full"]),
        "change": max(_relative(four["change"][k], one["change"][k])
                      for k in one["change"]),
        "corr_norm": _relative(four["corr_norm"], one["corr_norm"]),
        "failsafe_same": four["failsafe_ok"] == one["failsafe_ok"],
    }
    checks = lm_mlmc.compare(driver.reference(**driver.CONTROL), four,
                             run.limits)
    out["control"] = {"failed": [n for n, v, lim in checks
                                 if not v <= lim]}
    return out


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " "
                          "--xla_force_host_platform_device_count=4").strip())
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=1500)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("quantity", ["loss", "grad", "grad_full", "change",
                                      "corr_norm"])
def test_reference_four_devices_follows_one(readings, quantity):
    assert readings["parity"][quantity] <= PARITY_RTOL, readings["parity"]


def test_reference_four_devices_same_failsafe(readings):
    assert readings["parity"]["failsafe_same"] is True


def test_intact_is_correct(readings):
    assert readings["intact"]["correct"] is True, readings["intact"]


@pytest.mark.parametrize("fault", CASES[1:])
def test_fault_is_not_correct(readings, fault):
    assert readings[fault]["correct"] is False, readings[fault]


def test_control_is_not_correct(readings):
    assert readings["control"]["failed"], readings["control"]


if __name__ == "__main__":
    print(json.dumps(child()), flush=True)
