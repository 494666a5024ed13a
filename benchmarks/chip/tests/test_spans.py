"""The program-span reduction and the three host readers of the grid, on
hand-made records.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/tests/test_spans.py
"""
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from benchmarks.chip import run as bench_run  # noqa: E402
from benchmarks.chip import spans  # noqa: E402

US = 1000  # ns


def rec(name, start, end, parent=None, **counts):
    return (name, start * US, end * US, parent, counts)


# one sweep of two chunks, a second sweep, and spans outside any sweep
RECORDS = [
    rec("repro.sweep", 0, 1000),                      # 0: root
    rec("repro.sweep.chunk", 10, 500, 0),             # 1
    rec("repro.schedule", 20, 120, 1),                # 2
    rec("repro.batches", 120, 170, 1),                # 3
    rec("repro.dispatch", 170, 180, 1, traced=0),     # 4
    rec("repro.wait", 180, 400, 1),                   # 5
    rec("repro.results", 400, 480, 1),                # 6
    rec("repro.sweep.chunk", 500, 990, 0),            # 7
    rec("repro.schedule", 510, 600, 7),               # 8
    rec("repro.batches", 520, 560, 8),                # 9: nested in 8
    rec("repro.wait", 600, 900, 7),                   # 10
    rec("repro.results", 900, 980, 7),                # 11
    rec("repro.run", 2000, 2300),                     # 12: not a sweep
    rec("repro.schedule", 2000, 2100, 12),            # 13
    rec("repro.batches", 2500, 2600),                 # 14: a root of its own
    rec("repro.sweep", 3000, 3100),                   # 15: second root
    rec("repro.wait", 3010, 3090, 15),                # 16
]


def test_self_times_nested_and_two_roots():
    t = spans.self_times(RECORDS)
    assert t["roots"] == 2
    assert t["root_ns"] == (1000 + 100) * US
    s = t["self_ns"]
    assert s["repro.sweep"] == (1000 - 490 - 490 + 100 - 80) * US
    assert s["repro.sweep.chunk"] == (490 - 460 + 490 - 470) * US
    assert s["repro.schedule"] == (100 + 90 - 40) * US  # less its child
    assert s["repro.batches"] == (50 + 40) * US
    assert s["repro.wait"] == (220 + 300 + 80) * US
    assert s["repro.results"] == (80 + 80) * US
    assert s["repro.dispatch"] == 10 * US
    # every nanosecond of the roots lands in exactly one self time
    assert sum(s.values()) == t["root_ns"]
    assert "repro.run" not in s  # outside any sweep tree: ignored


def test_missing_root():
    outside = RECORDS[12:15]
    assert spans.self_times(outside) is None
    assert spans.self_times([]) is None
    assert spans.self_times(None) is None
    # a repro.sweep span with a parent is no root
    assert spans.self_times([rec("repro.run", 0, 10),
                             rec("repro.sweep", 1, 2, 0)]) is None


def ctx(cell_rounds):
    return types.SimpleNamespace(
        work={"amounts": {"cell_rounds_per_s": cell_rounds}})


@pytest.fixture
def recorded(monkeypatch):
    def use(records):
        monkeypatch.setattr(spans, "program_records", lambda: records)
    return use


def test_readers(recorded):
    recorded(RECORDS)
    c = ctx(10)
    read = {n: bench_run.load_metric(n).read(c) for n in (
        "host_inputs_us.sweep", "host_results_us.sweep",
        "host_other_us.sweep")}
    assert read["host_inputs_us.sweep"] == pytest.approx(
        (100 + 90 - 40 + 50 + 40) / 10)
    assert read["host_results_us.sweep"] == pytest.approx(160 / 10)
    # the roots' 1100 us less inputs 240, results 160 and waits 600
    assert read["host_other_us.sweep"] == pytest.approx(100 / 10)


def test_readers_without_sweep_records(recorded):
    for records in (None, [], RECORDS[12:15]):
        recorded(records)
        for n in ("host_inputs_us.sweep", "host_results_us.sweep",
                  "host_other_us.sweep"):
            assert bench_run.load_metric(n).read(ctx(10)) is None, n


def test_program_records_reads_the_program():
    """On this tree the program has ``repro.obs``; with no profiler session
    it recorded nothing."""
    from repro import obs

    obs.clear()
    assert spans.program_records() == []
