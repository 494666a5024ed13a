"""Host spans and counters (``repro.obs``) and where the drivers put them.

Contracts locked here:

- with no profiler session nothing is recorded;
- parents follow the thread's own nesting, and self times add up;
- ``Session.sweep`` (lane chunks, rule groups, replicates) and
  ``Session.run`` (with eval callouts) record every span of the table in
  README "Tracing", each with its event of the same bare name on the
  profile's host plane;
- ``compiles`` counts the XLA compiles inside a span;
- the numbers are bitwise the same with the profiler on and off;
- every name starts with ``repro.`` and none is a name the benchmark's
  trace reduction reserves for its own spans.
"""
import collections
import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api.session import Session, _task_sampler_factory
from repro.api.specs import SweepSpec
from repro.core.mlmc import MLMCConfig
from repro.core import robust_train as rt
from repro.core.robust_train import DynaBROConfig, make_dynabro_scan_fn
from repro.core.scenarios import make_quadratic_task
from repro.core.switching import get_switcher
from repro.optim.optimizers import sgd

TASK = make_quadratic_task()
M = 8
T = 12
HARNESS_SPANS = {"window", "place", "dispatch", "wait", "sweep"}
SWEEP_SPANS = {"repro.sweep", "repro.sweep.chunk", "repro.sweep.group",
               "repro.schedule", "repro.batches", "repro.dispatch",
               "repro.wait", "repro.results"}
RUN_SPANS = {"repro.run", "repro.schedule", "repro.batches",
             "repro.dispatch", "repro.wait", "repro.eval", "repro.results"}


@pytest.fixture(autouse=True)
def fresh_records():
    obs.clear()
    yield
    obs.clear()


def _cfg():
    return DynaBROConfig(mlmc=MLMCConfig(T=T, m=M, V=3.0, kappa=1.0),
                         aggregator="cwmed", delta=0.45, attack="sign_flip")


def _session(**kw):
    return Session(_cfg(), grad_fn=TASK.grad_fn, params0=TASK.params0,
                   opt=sgd(2e-2), m=M, sample_batches=TASK.make_sampler(M),
                   sampler_factory=_task_sampler_factory(TASK, M), seed=0,
                   **kw)


# one compiled program per rule, shared by every sweep of this file
GROUP_FNS = {rule: make_dynabro_scan_fn(TASK.grad_fn, _cfg(), sgd(2e-2),
                                        lane_aggregators=(rule,))
             for rule in ("cwmed", "cwtm")}


def _two_rule_spec(scan_fn=GROUP_FNS):
    """Four cells, rules alternating, so each 2-cell chunk holds two rule
    groups; two replicate seeds."""
    return SweepSpec(
        switchers=tuple(("periodic", dict(n_byz=3, K=k)) for k in (3, 3, 5, 5)),
        aggregators=("cwmed", ("cwtm", {"delta": 0.3})) * 2, seeds=(0, 1),
        scan_fn=scan_fn)


def _sweep():
    return _session().sweep(_two_rule_spec(), T, lane_chunk=2)


def _host_events(trace_dir):
    """name -> [(start_ns, duration_ns, stats)] of the ``repro.`` events on
    the profile's host plane."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out[ev.name].append((ev.start_ns, ev.duration_ns,
                                         dict(ev.stats)))
    return out


def _self_ns(recs):
    """Each record's duration less what its children cover."""
    own = [r.end_ns - r.start_ns for r in recs]
    for r in recs:
        if r.parent is not None:
            own[r.parent] -= r.end_ns - r.start_ns
    return own


def _leaves(outs):
    return [np.asarray(leaf) for cell in outs for p, _ in cell
            for leaf in jax.tree.leaves(p)]


def _logs(outs):
    return [[(lg.level, lg.failsafe_ok, lg.n_byz, lg.cost) for lg in logs]
            for cell in outs for _, logs in cell]


def test_nothing_recorded_without_profiler():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with obs.span("repro.outer", lanes=3) as s:
        s.add("lanes", 2)
        with obs.span("repro.inner"):
            pass
    _sweep()
    assert obs.records() == []
    assert obs.dropped() == 0


def test_nested_spans_on_two_threads(tmp_path):
    """Each thread's inner span has that thread's outer span as parent;
    self time is the duration less the child's."""
    barrier = threading.Barrier(2)

    def work(tag):
        with obs.span(f"repro.outer.{tag}") as s:
            barrier.wait()
            time.sleep(0.02)
            with obs.span(f"repro.inner.{tag}", lanes=1) as inner:
                inner.add("lanes", 2)
                time.sleep(0.03)
            s.add("done")

    with jax.profiler.trace(str(tmp_path)):
        threads = [threading.Thread(target=work, args=(tag,))
                   for tag in "ab"]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    recs = obs.records()
    by_name = {r.name: i for i, r in enumerate(recs)}
    assert len(recs) == 4 and len(by_name) == 4
    own = _self_ns(recs)
    for tag in "ab":
        outer, inner = by_name[f"repro.outer.{tag}"], by_name[f"repro.inner.{tag}"]
        assert recs[outer].parent is None
        assert recs[inner].parent == outer
        assert recs[inner].counts == {"lanes": 3, "compiles": 0}
        assert recs[outer].counts == {"done": 1, "compiles": 0}
        assert 0.03e9 <= own[inner] < 0.2e9
        assert 0.02e9 <= own[outer] < 0.2e9
        assert own[outer] + own[inner] == \
            recs[outer].end_ns - recs[outer].start_ns


def test_sweep_spans_match_the_profile(tmp_path, monkeypatch):
    # an empty wrapper cache: each rule's first dispatch traces anew
    monkeypatch.setattr(rt, "_VMAPPED_CACHE", [])
    with jax.profiler.trace(str(tmp_path)):
        _sweep()
    recs = obs.records()
    names = collections.Counter(r.name for r in recs)
    assert set(names) == SWEEP_SPANS
    # one root; two chunks of two groups; per group one segment
    assert names["repro.sweep"] == 1
    assert names["repro.sweep.chunk"] == 2
    assert names["repro.sweep.group"] == 4
    for leaf in ("repro.schedule", "repro.dispatch", "repro.wait",
                 "repro.results"):
        assert names[leaf] == 4, leaf
    assert names["repro.batches"] == 4 * 2  # one per replicate
    root, = [r for r in recs if r.name == "repro.sweep"]
    assert root.counts["lanes"] == 8 and root.counts["rounds"] == T
    assert [r.counts["lanes"] for r in recs
            if r.name == "repro.sweep.chunk"] == [4, 4]
    assert all(r.counts["lanes"] == 2 for r in recs
               if r.name in ("repro.sweep.group", "repro.results"))
    assert [r.counts["traced"] for r in recs
            if r.name == "repro.dispatch"] == [1, 1, 0, 0]
    assert all(r.counts.get("fallback", 0) == 0 for r in recs
               if r.name == "repro.batches")
    parents = {recs[r.parent].name if r.parent is not None else None
               for r in recs if r.name == "repro.dispatch"}
    assert parents == {"repro.sweep.group"}

    events = _host_events(tmp_path)
    assert set(events) == set(names)
    for name, n in names.items():
        evs = sorted(events[name])
        rs = sorted((r.start_ns, r.end_ns - r.start_ns, r.counts)
                    for r in recs if r.name == name)
        assert len(evs) == n, name
        for (_, ev_ns, stats), (_, rec_ns, counts) in zip(evs, rs):
            assert abs(ev_ns - rec_ns) <= max(0.05 * ev_ns, 50_000), name
            # counts given at entry ride along as the event's metadata
            for k, v in stats.items():
                assert counts[k] == v, (name, k)


def _two_leaf_grad(params, unit_key):
    g = TASK.grad_fn({"x": params["x"]}, unit_key)
    return {"x": g["x"], "b": 0.5 * params["b"]}


def test_results_copy_the_carry_once_per_sub_sweep(tmp_path):
    """``repro.results`` counts one host copy per parameter leaf, once per
    sub-sweep (two chunks of two rule groups), not once per lane."""
    params0 = {"x": TASK.params0["x"], "b": jnp.arange(3.0)}
    sess = Session(_cfg(), grad_fn=_two_leaf_grad, params0=params0,
                   opt=sgd(2e-2), m=M, sample_batches=TASK.make_sampler(M),
                   sampler_factory=_task_sampler_factory(TASK, M), seed=0)
    with jax.profiler.trace(str(tmp_path)):
        outs = sess.sweep(_two_rule_spec(scan_fn=None), T, lane_chunk=2)
    results = [r for r in obs.records() if r.name == "repro.results"]
    assert len(results) == 4
    n_leaves = len(jax.tree.leaves(params0))
    assert n_leaves == 2
    assert [r.counts["copies"] for r in results] == [n_leaves] * 4
    assert [r.counts["lanes"] for r in results] == [2] * 4
    assert all(type(leaf) is np.ndarray and not leaf.flags.writeable
               for cell in outs for p, _ in cell
               for leaf in jax.tree.leaves(p))


def test_run_spans(tmp_path):
    evals = []
    sess = _session(switcher=get_switcher("periodic", M, n_byz=3, K=4))
    with jax.profiler.trace(str(tmp_path)):
        sess.run(T, eval_fn=lambda p, t: evals.append(t) or 0.0,
                 eval_every=4)
    recs = obs.records()
    names = collections.Counter(r.name for r in recs)
    assert set(names) == RUN_SPANS
    assert names["repro.run"] == 1 and names["repro.eval"] == 3
    for seg in ("repro.batches", "repro.dispatch", "repro.wait"):
        assert names[seg] == 3, seg
    root, = [r for r in recs if r.name == "repro.run"]
    assert root.counts["rounds"] == T
    assert all(r.parent == recs.index(root) for r in recs if r is not root)
    assert evals == [3, 7, 11]


def test_compiles_counted(tmp_path):
    bump = float(np.random.default_rng().integers(1, 1 << 30))
    f = jax.jit(lambda x: x * 3.0 + bump)
    x = jnp.ones(5)
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("repro.fresh"):
            f(x).block_until_ready()
        with obs.span("repro.warm"):
            f(x).block_until_ready()
    fresh, warm = obs.records()
    assert fresh.name == "repro.fresh" and fresh.counts["compiles"] >= 1
    assert warm.name == "repro.warm" and warm.counts["compiles"] == 0


def test_sweep_bitwise_with_profiler_on_and_off(tmp_path):
    off = _sweep()
    with jax.profiler.trace(str(tmp_path)):
        on = _sweep()
    assert obs.records()
    assert _logs(on) == _logs(off)
    for a, b in zip(_leaves(on), _leaves(off), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_names_are_program_names(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        _sweep()
        _session(switcher=get_switcher("periodic", M, n_byz=3, K=4)).run(
            T, eval_fn=lambda p, t: 0.0, eval_every=6)
        _session().sweep_halving(_two_rule_spec(scan_fn=None), T,
                                 objective=TASK.objective)
    names = {r.name for r in obs.records()}
    assert {"repro.sweep", "repro.run", "repro.sweep_halving"} <= names
    assert all(n.startswith("repro.") for n in names), names
    assert not names & HARNESS_SPANS


def test_records_are_capped(tmp_path, monkeypatch):
    monkeypatch.setattr(obs, "MAX_RECORDS", 3)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(5):
            with obs.span("repro.tick"):
                pass
    assert len(obs.records()) == 3
    assert obs.dropped() == 2
    obs.clear()
    assert obs.records() == [] and obs.dropped() == 0


def test_threads_lose_no_record(tmp_path, monkeypatch):
    """More threads than cores, switching often: every span is either
    recorded or counted as dropped, and each keeps its own thread's
    parent."""
    import os
    import sys

    monkeypatch.setattr(obs, "MAX_RECORDS", 1000)
    n_threads, n_spans = 2 * (os.cpu_count() or 4), 100

    def work(k):
        for _ in range(n_spans // 2):
            with obs.span(f"repro.outer.{k}"):
                with obs.span(f"repro.inner.{k}"):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with jax.profiler.trace(str(tmp_path)):
            threads = [threading.Thread(target=work, args=(k,))
                       for k in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    recs = obs.records()
    assert len(recs) == 1000
    assert len(recs) + obs.dropped() == n_threads * n_spans
    for r in recs:
        if r.name.startswith("repro.inner."):
            if r.parent is not None:
                assert recs[r.parent].name == \
                    "repro.outer." + r.name.split(".")[-1]
        else:
            assert r.parent is None
