"""The replicate statistics axis and its scaling knobs (DESIGN.md §12).

Contracts locked here:

- **Non-degeneracy**: replicate lanes fold genuinely distinct mask / attack
  key / data streams, so per-cell trajectories differ across seeds and the
  reported std is positive.
- **Replicate parity**: replicate lane r of a replicated sweep is bitwise
  the single-lane sweep run with ``seeds=(s_r,)`` alone — and with the
  session's own seed, bitwise the un-replicated sweep (the R==1 fast path
  preserves the pre-replicate schedule stream exactly).
- **Chunk invariance**: ``lane_chunk=`` streams a grid through fixed-size
  dispatches with host-side accumulation and is bitwise-invisible.
- **Mesh contract**: a 1-device ``make_lane_mesh`` is bitwise the unsharded
  sweep (in-process); multi-device lane sharding is bitwise too (subprocess
  with forced host devices, same pattern as test_scan_driver_sharded.py).
- **Halving**: successive-halving survivors are bitwise a plain sweep of
  the surviving subset; pruned cells report their state at the pruning rung.
- **Host results**: every sweep's per-lane finals are read-only host numpy
  views, bitwise the eager per-lane slices of the sub-sweep's stacked carry;
  the batched round-log builder equals the single-lane one lane by lane.
- **Reporting**: run_matrix(driver="vmap") rows carry mean/std/stderr and
  n_seeds; format_table renders the error bar only for n_seeds >= 2; the
  per-cell drivers reject the replicate kwargs.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.api import session as session_mod
from repro.api.session import Session, _task_sampler_factory
from repro.api.specs import SweepSpec
from repro.core import robust_train as rt
from repro.core.mlmc import MLMCConfig, round_cost
from repro.core.robust_train import DynaBROConfig, make_dynabro_scan_fn
from repro.core.scenarios import (
    format_table, make_quadratic_task, run_matrix, scenario_grid,
)
from repro.core.switching import Switcher, get_switcher
from repro.launch.mesh import make_lane_mesh, make_mesh
from repro.optim.optimizers import sgd

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

TASK = make_quadratic_task()
M = 8
T = 32

SWS = tuple(("periodic", dict(n_byz=3, K=k)) for k in (4, 8, 16))


def _cfg():
    return DynaBROConfig(
        mlmc=MLMCConfig(T=T, m=M, V=3.0, kappa=1.0),
        aggregator="cwmed", delta=0.45, attack="sign_flip")


def _sess(**kw):
    kw.setdefault("sampler_factory", _task_sampler_factory(TASK, M))
    return Session(_cfg(), grad_fn=TASK.grad_fn, params0=TASK.params0,
                   opt=sgd(2e-2), m=M,
                   sample_batches=TASK.make_sampler(M), seed=0, **kw)


def _x(p):
    return np.asarray(p["x"])


def _assert_logs_equal(l1, l2):
    assert [l.level for l in l1] == [l.level for l in l2]
    assert [l.failsafe_ok for l in l1] == [l.failsafe_ok for l in l2]
    assert [l.n_byz for l in l1] == [l.n_byz for l in l2]
    assert [l.cost for l in l1] == [l.cost for l in l2]


def _assert_cells_equal(a, b):
    """a, b: [[(params, logs), ...] per cell] in matching order."""
    assert len(a) == len(b)
    for ca, cb in zip(a, b):
        assert len(ca) == len(cb)
        for (pa, la), (pb, lb) in zip(ca, cb):
            np.testing.assert_array_equal(_x(pa), _x(pb))
            _assert_logs_equal(la, lb)


# ------------------------------------------------------------ non-degeneracy


def test_replicate_lanes_differ_and_std_positive():
    outs = _sess().sweep(SweepSpec(switchers=SWS, seeds=(0, 1, 2)), T)
    assert len(outs) == len(SWS)
    for cell in outs:
        assert len(cell) == 3
        finals = [TASK.objective(p) for p, _ in cell]
        # distinct seeds -> distinct mask/key/batch streams -> distinct lanes
        for i in range(3):
            for j in range(i + 1, 3):
                assert not np.array_equal(_x(cell[i][0]), _x(cell[j][0]))
        assert np.std(finals, ddof=1) > 0.0


def test_replicates_count_derives_seeds():
    sess = _sess()
    by_count = sess.sweep(SweepSpec(switchers=SWS[:1], replicates=2), T)
    by_seeds = sess.sweep(
        SweepSpec(switchers=SWS[:1], seeds=(sess.seed, sess.seed + 1)), T)
    _assert_cells_equal(by_count, by_seeds)


# ----------------------------------------------------------- replicate parity


def test_replicate_lane_matches_single_seed_sweep():
    """Lane r of the replicated sweep == the whole sweep re-run with only
    seed s_r — replicates are independent, just batched into one dispatch."""
    sess = _sess()
    seeds = (0, 3, 11)
    rep = sess.sweep(SweepSpec(switchers=SWS, seeds=seeds), T)
    for r, s in enumerate(seeds):
        solo = sess.sweep(SweepSpec(switchers=SWS, seeds=(s,)), T)
        _assert_cells_equal([[cell[r]] for cell in rep],
                            [[c] for c in solo])


def test_session_seed_replicate_is_bitwise_the_plain_sweep():
    """seeds=(session.seed,) must reproduce the un-replicated sweep exactly:
    the R==1 path folds the same streams the plain path draws."""
    sess = _sess()
    plain = sess.sweep(SweepSpec(switchers=SWS), T)
    rep = sess.sweep(SweepSpec(switchers=SWS, seeds=(sess.seed,)), T)
    _assert_cells_equal([[c] for c in plain], [[c] for c in rep])


def test_replicates_need_per_replicate_samplers():
    sess = _sess(sampler_factory=None)
    with pytest.raises(ValueError, match="sampler"):
        sess.sweep(SweepSpec(switchers=SWS, seeds=(1, 2)), T)


def test_switcher_instances_reject_replication():
    sw = get_switcher("periodic", M, n_byz=3, K=8)
    spec = SweepSpec(switchers=(sw,), seeds=(0, 1))
    with pytest.raises(ValueError, match="(name, kwargs)"):
        _sess().sweep(spec, T)


def test_seed_validation():
    with pytest.raises(ValueError, match="duplicates"):
        SweepSpec(switchers=SWS, seeds=(0, 0, 1))
    with pytest.raises(ValueError, match="disagrees"):
        SweepSpec(switchers=SWS, seeds=(0, 1), replicates=3)
    with pytest.raises(ValueError, match=">= 1"):
        SweepSpec(switchers=SWS, replicates=0)


# ----------------------------------------------------------- chunk invariance


def test_lane_chunk_is_bitwise_invisible():
    sess = _sess()
    sws = tuple(("periodic", dict(n_byz=3, K=k)) for k in (4, 6, 8, 12, 16, 24))
    spec = SweepSpec(switchers=sws, seeds=(0, 1))
    oneshot = sess.sweep(spec, T)
    for lane_chunk in (1, 2, 4, 5):
        chunked = sess.sweep(spec, T, lane_chunk=lane_chunk)
        _assert_cells_equal(oneshot, chunked)


def test_lane_chunk_composes_with_segment_chunk():
    sess = _sess()
    spec = SweepSpec(switchers=SWS, seeds=(0, 1))
    _assert_cells_equal(sess.sweep(spec, T),
                        sess.sweep(spec, T, chunk=8, lane_chunk=2))


def test_lane_chunk_mixed_rule_grouping():
    """Chunk boundaries cut across aggregator groups: each sub-sweep sees a
    subset of the rules and must still group branch-homogeneously."""
    sess = _sess()
    spec = SweepSpec(
        switchers=tuple(("periodic", dict(n_byz=3, K=k))
                        for k in (4, 8, 16, 24)),
        aggregators=("cwmed", "cwtm", "cwmed", "cwtm"),
        seeds=(0, 1))
    _assert_cells_equal(sess.sweep(spec, T),
                        sess.sweep(spec, T, lane_chunk=3))


def test_mapping_scan_fn_may_be_a_superset():
    """A {rule: scan_fn} mapping may carry more rules than a (chunked)
    sub-grid uses — required for lane_chunk to compose with grouping."""
    sess = _sess()
    fns = {rule: make_dynabro_scan_fn(TASK.grad_fn, _cfg(), sgd(2e-2),
                                      lane_aggregators=(rule,))
           for rule in ("cwmed", "cwtm")}
    spec = SweepSpec(switchers=SWS, aggregators=("cwmed",) * len(SWS),
                     scan_fn=fns)
    plain = sess.sweep(SweepSpec(switchers=SWS,
                                 aggregators=("cwmed",) * len(SWS)), T)
    _assert_cells_equal([[c] for c in plain],
                        [[c] for c in sess.sweep(spec, T)])
    with pytest.raises(ValueError, match="cover"):
        sess.sweep(SweepSpec(switchers=SWS, aggregators=("krum",) * len(SWS),
                             scan_fn=fns), T)


# --------------------------------------------------------------- lane meshes


def test_one_device_lane_mesh_is_bitwise():
    """The acceptance contract: a 1-device lane mesh normalizes away and is
    bitwise the unsharded sweep."""
    sess = _sess()
    spec = SweepSpec(switchers=SWS, seeds=(0, 1))
    _assert_cells_equal(sess.sweep(spec, T),
                        sess.sweep(spec, T, lane_mesh=make_lane_mesh(1, 1)))


def test_lane_mesh_validation():
    sess = _sess()
    with pytest.raises(ValueError, match="lanes"):
        sess.sweep(SweepSpec(switchers=SWS, seeds=(0, 1)), T,
                   lane_mesh=make_mesh((1,), ("data",)))


def _run(body: str):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import jax, numpy as np
        from repro.api.session import Session, _task_sampler_factory
        from repro.api.specs import SweepSpec
        from repro.core.mlmc import MLMCConfig
        from repro.core.robust_train import DynaBROConfig
        from repro.core.scenarios import make_quadratic_task
        from repro.launch.mesh import make_lane_mesh
        from repro.optim.optimizers import sgd
        T, m = 32, 8
        task = make_quadratic_task()
        cfg = DynaBROConfig(mlmc=MLMCConfig(T=T, m=m, V=3.0, kappa=1.0),
                            aggregator="cwmed", delta=0.45, attack="sign_flip")
        sess = Session(cfg, grad_fn=task.grad_fn, params0=task.params0,
                       opt=sgd(2e-2), m=m, sample_batches=task.make_sampler(m),
                       seed=0, sampler_factory=_task_sampler_factory(task, m))
        sws = tuple(("periodic", dict(n_byz=3, K=k)) for k in (4, 8, 16, 24))
        spec = SweepSpec(switchers=sws, seeds=(0, 1))
        def cells_equal(a, b, exact=True):
            assert len(a) == len(b)
            for ca, cb in zip(a, b):
                for (pa, la), (pb, lb) in zip(ca, cb):
                    xa, xb = np.asarray(pa["x"]), np.asarray(pb["x"])
                    if exact:
                        np.testing.assert_array_equal(xa, xb)
                    else:
                        np.testing.assert_allclose(xa, xb, rtol=1e-6)
                    assert [l.level for l in la] == [l.level for l in lb]
                    assert [l.n_byz for l in la] == [l.n_byz for l in lb]
    """ % SRC) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-4000:] + "\n" + r.stderr[-4000:]
    return r.stdout


@pytest.mark.slow
def test_lane_mesh_multi_device_parity():
    """4 cells x 2 replicates: sharding the cell axis across real devices is
    bitwise (lanes are independent programs laid side by side); adding a
    sharded worker axis keeps numerics within the parity band."""
    _run("""
        base = sess.sweep(spec, T)
        for n_lanes in (2, 4):
            sharded = sess.sweep(spec, T, lane_mesh=make_lane_mesh(n_lanes, 1))
            cells_equal(base, sharded)
        mixed = sess.sweep(spec, T, lane_mesh=make_lane_mesh(2, 2))
        cells_equal(base, mixed, exact=False)
        print("OK")
    """)


@pytest.mark.slow
def test_lane_mesh_rejects_indivisible_lane_count():
    _run("""
        try:
            sess.sweep(spec, T, lane_mesh=make_lane_mesh(3, 1))
        except ValueError as e:
            assert "divisible" in str(e), e
            print("OK")
        else:
            raise SystemExit("expected ValueError: 4 cells on a 3-way axis")
    """)


# ------------------------------------------------------- successive halving


def test_halving_prunes_and_survivors_are_bitwise():
    sess = _sess()
    sws = tuple(("periodic", dict(n_byz=b, K=k))
                for b, k in ((3, 4), (3, 8), (3, 16), (5, 4), (5, 8), (5, 16)))
    spec = SweepSpec(switchers=sws, seeds=(0, 1))
    out = sess.sweep_halving(spec, T, objective=TASK.objective, keep=0.5)
    assert len(out) == 6
    pruned = [o for o in out if o["pruned"]]
    alive = [o for o in out if not o["pruned"]]
    assert len(pruned) == 3 and len(alive) == 3
    assert all(o["rounds_run"] == T // 2 for o in pruned)
    assert all(o["rounds_run"] == T for o in alive)
    # survivors are bitwise a plain sweep of the full grid (lane-subset
    # invariance: pruning other lanes cannot perturb a survivor)
    full = sess.sweep(spec, T)
    for i, o in enumerate(out):
        if not o["pruned"]:
            _assert_cells_equal([o["results"]], [full[i]])


def test_halving_scores_on_replicate_mean():
    """keep=1.0 prunes nothing and reproduces the plain sweep end-state."""
    sess = _sess()
    spec = SweepSpec(switchers=SWS, seeds=(0, 1))
    out = sess.sweep_halving(spec, T, objective=TASK.objective, keep=1.0)
    assert all(not o["pruned"] and o["rounds_run"] == T for o in out)
    _assert_cells_equal([o["results"] for o in out], sess.sweep(spec, T))


def test_halving_validation():
    sess = _sess()
    spec = SweepSpec(switchers=SWS)
    with pytest.raises(ValueError, match="keep"):
        sess.sweep_halving(spec, T, objective=TASK.objective, keep=0.0)
    with pytest.raises(ValueError, match="rungs"):
        sess.sweep_halving(spec, T, objective=TASK.objective, rungs=[T])
    with pytest.raises(ValueError, match="rungs"):
        sess.sweep_halving(spec, T, objective=TASK.objective, rungs=[8, 8])
    fns = {"cwmed": None}
    with pytest.raises(ValueError, match="mapping"):
        sess.sweep_halving(SweepSpec(switchers=SWS, scan_fn=fns), T,
                           objective=TASK.objective)


# -------------------------------------------------------------- host results

HALVING_SWS = tuple(("periodic", dict(n_byz=b, K=k))
                    for b, k in ((3, 4), (3, 8), (3, 16), (5, 4), (5, 8),
                                 (5, 16)))

# case -> (sweep call, its finals in caller order)
HOST_CASES = {
    "plain": lambda s: [p for p, _ in s.sweep(SweepSpec(switchers=SWS), T)],
    "replicated": lambda s: [p for c in s.sweep(
        SweepSpec(switchers=SWS, seeds=(0, 1)), T) for p, _ in c],
    "lane_chunk": lambda s: [p for c in s.sweep(
        SweepSpec(switchers=SWS, seeds=(0, 1)), T, lane_chunk=2)
        for p, _ in c],
    "rule_groups": lambda s: [p for c in s.sweep(
        SweepSpec(switchers=SWS + SWS[:1],
                  aggregators=("cwmed", "cwtm", "cwmed", "cwtm"),
                  seeds=(0, 1)), T, lane_chunk=3) for p, _ in c],
    "halving": lambda s: [p for o in s.sweep_halving(
        SweepSpec(switchers=HALVING_SWS, seeds=(0, 1)), T,
        objective=TASK.objective, keep=0.5) for p, _ in o["results"]],
    "halving_plain": lambda s: [p for o in s.sweep_halving(
        SweepSpec(switchers=HALVING_SWS), T, objective=TASK.objective,
        keep=0.5) for p, _ in o["results"]],
}


@pytest.mark.parametrize("case", sorted(HOST_CASES))
def test_sweep_finals_are_readonly_host_views_of_the_carry(case,
                                                           monkeypatch):
    """Each sub-sweep's finals are read-only numpy views of one host copy,
    bitwise the eager ``l[c]`` / ``l[c, r]`` device slices the per-lane
    scatter used to take, lane for lane."""
    calls = []
    real = session_mod._lane_results

    def recording(params, levels, ok, masks, j_max, span):
        out = real(params, levels, ok, masks, j_max, span)
        calls.append((params, ok.shape[:-1], out))
        return out

    monkeypatch.setattr(session_mod, "_lane_results", recording)
    finals = HOST_CASES[case](_sess())
    views = {}
    for params, lead, out in calls:
        for idx in np.ndindex(*lead):
            lane = out[idx[0]][idx[1]] if len(lead) == 2 else out[idx[0]]
            eager = jax.tree.map(lambda l: l[idx], params)
            for got, want in zip(jax.tree.leaves(lane[0]),
                                 jax.tree.leaves(eager), strict=True):
                assert type(got) is np.ndarray
                assert not got.flags.writeable
                assert got.dtype == want.dtype
                np.testing.assert_array_equal(got, np.asarray(want))
            views[id(lane[0])] = lane[0]
    # every lane the sweep hands back is one of those views (halving also
    # copies every live lane at its rung)
    if not case.startswith("halving"):
        assert len(finals) == len(views)
    for p in finals:
        assert views.get(id(p)) is p
        with pytest.raises(ValueError):
            p["x"][0] = 0.0


class _Flipper(Switcher):
    """A stateful within-round switcher: each call flips which half of the
    workers is Byzantine, so only the replayed call sequence is exact."""

    def __init__(self, m):
        super().__init__(m)
        self.calls = 0

    def mask(self, t):
        return np.zeros(self.m, bool)

    def within_round(self, t, k):
        self.calls += 1
        mk = np.zeros(self.m, bool)
        mk[: self.m // 2] = self.calls % 2 == 1
        mk[-1] = (t + k) % 3 == 0
        return mk


def _loop_logs(levels, ok, masks, j_max):
    """The per-round loop the batched builder replaced."""
    return [rt.RoundLog(int(levels[t]), bool(ok[t]), int(masks[t, 0].sum()),
                        round_cost(int(levels[t]), j_max))
            for t in range(len(levels))]


@pytest.mark.parametrize("lead,within,seed", [
    ((5,), False, 0), ((3, 4), False, 1), ((4,), True, 2),
    ((2, 3), True, 3), ((1,), False, 4)])
def test_round_logs_lanes_equal_single_lane(lead, within, seed):
    rng = np.random.default_rng(seed)
    j_max, n_max, m, T_ = 3, 8, 6, 40
    # levels up to j_max + 2: beyond-cap rounds cost 1
    levels = rng.integers(0, j_max + 3, T_).astype(np.int32)
    assert (levels > j_max).any()
    ok = rng.random(lead + (T_,)) < 0.7
    if within:
        ns = np.where(levels <= j_max, 2 ** levels.astype(np.int64), 1)
        masks = np.stack([rt._mask_schedule(_Flipper(m), T_ + 3, n_max,
                                            np.append(ns, [1, 1, 1]))
                          for _ in range(int(np.prod(lead)))])
        masks = masks.reshape(lead + masks.shape[1:])
    else:
        masks = rng.random(lead + (T_ + 3, n_max, m)) < 0.3
    batched = rt._round_logs_lanes(levels, ok, masks, j_max)
    assert len(batched) == int(np.prod(lead))
    for logs, idx in zip(batched, np.ndindex(*lead), strict=True):
        single = rt._round_logs(levels, ok[idx], masks[idx], j_max)
        assert logs == single == _loop_logs(levels, ok[idx], masks[idx],
                                            j_max)
        assert all(type(lg.level) is int and type(lg.failsafe_ok) is bool
                   and type(lg.n_byz) is int and type(lg.cost) is int
                   for lg in logs)
    assert any(lg.cost == 1 and lg.level > j_max for lg in batched[0])


# ----------------------------------------------------- reporting / run_matrix


def _grid():
    return scenario_grid(["sign_flip"], [("periodic", {"n_byz": 3, "K": 8}),
                                         ("static", {"n_byz": 3})], ["cwmed"])


def test_run_matrix_vmapped_stats_columns():
    rows = run_matrix(TASK, _grid(), m=M, T=T, V=3.0, driver="vmap",
                      seeds=(0, 1, 2))
    for r in rows:
        assert r["n_seeds"] == 3
        assert r["final"] == r["final_mean"]
        assert r["final_std"] > 0.0
        np.testing.assert_allclose(r["final_stderr"],
                                   r["final_std"] / np.sqrt(3.0))


def test_run_matrix_vmapped_single_seed_row_is_bitwise():
    plain = run_matrix(TASK, _grid(), m=M, T=T, V=3.0, driver="vmap")
    for r in plain:
        assert r["n_seeds"] == 1
        assert r["final_std"] == 0.0 and r["final_stderr"] == 0.0
        assert r["final"] == r["final_mean"]
    # the replicate axis left un-used must not perturb the row values
    again = run_matrix(TASK, _grid(), m=M, T=T, V=3.0, driver="vmap")
    assert [r["final"] for r in plain] == [r["final"] for r in again]


def test_per_cell_drivers_reject_replicate_kwargs():
    with pytest.raises(ValueError, match="vmap"):
        run_matrix(TASK, _grid(), m=M, T=T, V=3.0, driver="scan",
                   seeds=(0, 1))
    with pytest.raises(ValueError, match="vmap"):
        run_matrix(TASK, _grid(), m=M, T=T, V=3.0, driver="scan",
                   lane_chunk=4)


def test_format_table_error_bars():
    grid = scenario_grid(["sign_flip", "ipm"],
                         [("periodic", {"n_byz": 3, "K": 8})], ["cwmed"])
    rows = run_matrix(TASK, grid, m=M, T=T, V=3.0, driver="vmap",
                      seeds=(0, 1, 2))
    table = format_table(rows)
    assert "±" in table
    single = format_table(run_matrix(TASK, grid, m=M, T=T, V=3.0,
                                     driver="vmap"))
    assert "±" not in single
