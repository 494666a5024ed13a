"""The trace reduction on hand-made intervals and on a recorded extract.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/tests/test_trace.py
"""
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from benchmarks.chip import trace  # noqa: E402


def test_union_and_subtract():
    assert trace.union([[5, 30], [25, 40], [50, 60], [55, 70]]) == \
        [[5, 40], [50, 70]]
    assert trace.subtract([[0, 100]], [[5, 40], [50, 70]]) == \
        [[0, 5], [40, 50], [70, 100]]
    assert trace.subtract([[25, 40]], [[5, 30], [50, 70]]) == [[30, 40]]
    assert trace.subtract([[0, 10]], []) == [[0, 10]]


def _hand_made():
    ns = 10 ** 9  # whole seconds, to read the results plainly
    return {
        "devices": {0: [["dot", 5 * ns, 30 * ns, "compute"],
                        ["all-to-all", 25 * ns, 40 * ns, "collective"],
                        ["cwtm", 50 * ns, 60 * ns, "custom"],
                        ["fusion", 55 * ns, 70 * ns, "compute"],
                        ["late", 120 * ns, 130 * ns, "compute"]]},
        "spans": [["window", 0, 100 * ns], ["place", 0, 10 * ns],
                  ["dispatch", 10 * ns, 12 * ns], ["wait", 12 * ns, 100 * ns]],
    }


def test_reduce_hand_made():
    r = trace.reduce(_hand_made(), n_devices=1)
    near = pytest.approx
    assert r["window_s"] == near(100)
    assert r["busy_s"] == r["busy_s_dev0"] == near(55)  # [5,40] + [50,70]
    assert r["custom_s"] == near(10)
    assert r["collective_s"] == near(15)
    assert r["collective_exposed_s"] == near(10)  # [30,40]: nothing else
    assert [n for n, _ in r["top_gaps"]] == ["wait", "wait", "place"]
    assert [s for _, s in r["top_gaps"]] == near([30, 10, 5])
    assert r["idle_by_span"] == near({"wait": 40, "place": 5})
    assert r["top_ops"][0] == ["dot", near(25)]


def test_reduce_nested():
    """A loop holds its body's operations: busy time counts the loop, the
    sums and the exposed collective time count what it holds."""
    ns = 10 ** 9
    ext = {
        "devices": {0: [["while.1", 0, 50 * ns, "compute"],
                        ["fusion.2", 0, 20 * ns, "compute"],
                        ["all-gather.3", 20 * ns, 30 * ns, "collective"],
                        ["custom-call.4", 30 * ns, 45 * ns, "custom"]]},
        "spans": [["window", 0, 60 * ns], ["wait", 0, 60 * ns]],
    }
    r = trace.reduce(ext, n_devices=1)
    near = pytest.approx
    assert r["busy_s"] == near(50)
    assert r["collective_exposed_s"] == near(10)
    assert r["custom_s"] == near(15)
    assert [n for n, _ in r["top_ops"]] == ["fusion.2", "custom-call.4",
                                            "all-gather.3"]
    assert r["top_gaps"] == [["wait", near(10)]]


def collective_exposed(reduced):
    """``collective_exposed.train`` as the harness reads it."""
    from benchmarks.chip import run as bench_run

    return bench_run.load_metric("collective_exposed.train").read(
        types.SimpleNamespace(trace=reduced))


def test_collective_exposed_hand_made():
    """10 of the window's 100 s hold the all-to-all alone; without a
    collective the metric reads nothing, never 0."""
    ext = _hand_made()
    assert collective_exposed(trace.reduce(ext, n_devices=1)) == \
        pytest.approx(10.0)
    ext["devices"][0] = [op for op in ext["devices"][0]
                         if op[3] != "collective"]
    assert collective_exposed(trace.reduce(ext, n_devices=1)) is None


def test_short_name():
    assert trace.short_name("%fusion.12 = bf16[8]{0} fusion(%p), kind=kLoop",
                            "loop fusion") == "fusion.12 (loop fusion)"
    assert trace.short_name("cwtm", "") == "cwtm"


def test_op_kind():
    assert trace.op_kind("custom-call.3", "") == "custom"
    assert trace.op_kind("fusion.1", "custom-call") == "custom"
    assert trace.op_kind("all-to-all.2", "") == "collective"
    assert trace.op_kind("all-gather-start", "collective") == "collective"
    assert trace.op_kind("convolution.5", "convolution") == "compute"


def test_recorded_trace(tmp_path):
    """``extract`` on a trace recorded here, on the CPU: the harness's
    spans come back from the host plane in order, and ``reduce`` names
    every idle gap of a device that ran nothing by the span it fell in.
    (A TPU trace adds the device planes; their ops are read the same way.)"""
    import glob
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("dispatch"):
                y = f(x)
            with jax.profiler.TraceAnnotation("wait"):
                y.block_until_ready()
                time.sleep(0.01)
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    ext = trace.extract(path)
    assert [s[0] for s in ext["spans"]].count("window") == 1
    assert [s[0] for s in ext["spans"]].count("dispatch") == 2
    assert all(s[1] <= s[2] for s in ext["spans"])
    r = trace.reduce(ext, n_devices=1)
    assert r["window_s"] > 0.02 and r["busy_s"] == 0
    assert sum(s for _, s in r["top_gaps"]) == pytest.approx(r["window_s"])
    assert "wait" in r["idle_by_span"]
