#!/usr/bin/env python3
"""Run DynaBRO's main paths once on a TPU and check what comes out.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # the multi-chip paths, on four chips

Everything runs in this one process (a chip belongs to one process at a
time), phase by phase; each phase prints one line of what it found, and any
failure ends the run with a non-zero exit: nothing is caught and skipped.
Without a TPU the first phase fails. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

One chip:
  device   JAX version and devices; fails unless the platform is ``tpu``.
  kernels  the aggregation engine's primitives (``backend="auto"``) on an
           (m=17, d=2^22) f32 stack, each compiled to a Pallas TPU kernel and
           checked against ``kernels/ref.py``.
  mode_a   the paper's Algorithm 2 through ``build_session``/``Session.run``
           (m=17, 8 sign-flipping Byzantine workers, Periodic(10), CWTM,
           MLMC), then one ``Session.sweep`` of 8 cells x 2 seeds with no
           recompile in its steady state.
  serve    the aggregation service stream (``repro.serve.smoke``), served
           carry bitwise equal to the offline driver.
  mode_b   ``repro.launch.train`` at SmolLM-360M's published widths (bf16,
           seq 512, global batch 8) on a 1x1 mesh: level-0 and MLMC steps.

Four chips (``--four-chips``) runs only the multi-chip paths and their
references:
  mode_a_mesh  the Mode A driver on a 4-device workers mesh against the
               unsharded driver.
  mode_b_mesh  SmolLM-360M Mode B on a 4x1 mesh (m=4, one periodic
               sign-flipper, CWTM, MLMC) against the same run on 1x1.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

M_KERNEL, D_KERNEL = 17, 1 << 22
PARITY_TOL = 1e-6  # Mode A sharded vs unsharded (DESIGN.md §7)
BF16_LOSS_RTOL = 1e-2  # Mode B step-0 loss, 4x1 vs 1x1

_MODE_B = ["--arch", "smollm-360m", "--global-batch", "8", "--seq-len", "512",
           "--aggregator", "cwtm", "--attack", "sign_flip", "--seed", "0"]


def _peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", -1)


def _require_compiled_kernel(program, what: str) -> None:
    """A Pallas call lowered for the chip (neither interpret mode nor the
    XLA reference) shows up as a TPU custom call in the program's text."""
    if "tpu_custom_call" not in program.as_text():
        raise AssertionError(f"{what}: no tpu_custom_call in the compiled "
                             f"program")


# ---------------------------------------------------------------- phases


def phase_device(n_chips: int) -> str:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU, JAX found {devs}")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, found {devs}")
    return f"jax {jax.__version__} devices={devs}"


def phase_kernels() -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import agg_engine as E
    from repro.kernels import ref as kref

    kx, kw, kz = jax.random.split(jax.random.PRNGKey(0), 3)
    x = 3.0 * jax.random.normal(kx, (M_KERNEL, D_KERNEL), jnp.float32)
    w = jax.random.uniform(kw, (M_KERNEL, M_KERNEL), jnp.float32)
    w = w / w.sum(1, keepdims=True)
    z = x[:1] + 0.01 * jax.random.normal(kz, (1, D_KERNEL), jnp.float32)
    trim = jnp.int32(4)
    args = (x, w, z, trim)

    def pw_close(got, want):  # tests/test_agg_engine.py::_pw_close
        scale = want.max() + 1e-9
        np.testing.assert_allclose(got / scale, want / scale, atol=2e-6)

    def close(tol):
        return lambda got, want: np.testing.assert_allclose(
            got, want, rtol=tol, atol=tol)

    # name: (engine call, reference, comparison at the unit tests' tolerance)
    cases = {
        "cw_median": (lambda x, w, z, t: E.cw_median(x, backend="auto"),
                      lambda x, w, z, t: kref.cwmed_ref(x), close(1e-5)),
        "cwtm_static": (
            lambda x, w, z, t: E.cw_trimmed_mean(x, 4, backend="auto"),
            lambda x, w, z, t: kref.cwtm_ref(x, 4), close(1e-5)),
        "cwtm_traced": (
            lambda x, w, z, t: E.cw_trimmed_mean(x, t, backend="auto"),
            lambda x, w, z, t: kref.cwtm_ref(x, 4), close(1e-5)),
        "pairwise": (lambda x, w, z, t: E.pairwise_sqdist(x, backend="auto"),
                     lambda x, w, z, t: kref.pairwise_sqdist_ref(x), pw_close),
        "combine": (
            lambda x, w, z, t: E.weighted_combine(x, w, backend="auto"),
            lambda x, w, z, t: kref.weighted_combine_ref(x, w), close(1e-4)),
        "combine_reduce": (
            lambda x, w, z, t: E.combine_reduce(x, w, "tm", 4, backend="auto"),
            lambda x, w, z, t: kref.cwtm_ref(kref.weighted_combine_ref(x, w),
                                             4), close(1e-5)),
        "cross_sqdist": (
            lambda x, w, z, t: E.cross_sqdist(x, z, backend="auto"),
            lambda x, w, z, t: kref.cross_sqdist_ref(x, z), close(1e-4)),
    }
    found = []
    for name, (fn, ref_fn, check) in cases.items():
        compiled = jax.jit(fn).lower(*args).compile()
        _require_compiled_kernel(compiled, name)
        got = jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(*args))
        dt = time.perf_counter() - t0
        want = np.asarray(jax.jit(ref_fn)(*args))
        got = np.asarray(got)
        check(got, want)
        found.append(f"{name}: {dt * 1e3:.2f} ms "
                     f"max|diff|={np.abs(got - want).max():.3g}")
    return (f"m={M_KERNEL} d={D_KERNEL} f32, tpu_custom_call + ref parity: "
            + "; ".join(found) + f"; peak_bytes_in_use={_peak_bytes()}")


def phase_mode_a() -> str:
    from repro.api import (
        DynaBROConfig, MLMCConfig, SweepSpec, build_session, get_switcher,
        make_dynabro_scan_fn, sgd,
    )
    from repro.data.classification import make_index_sampler, make_task
    from repro.lint.runtime import recompile_guard

    # the examples/quickstart.py setting
    m, n_byz, T = 17, 8, 150
    params0, grad_fn, sampler, eval_fn = make_task(m, seed=0)
    cfg = DynaBROConfig(
        mlmc=MLMCConfig(T=T, m=m, V=5.0, option=1, kappa=1.0, j_cap=5),
        aggregator="cwtm", delta=n_byz / m + 1e-3, attack="sign_flip")
    opt = sgd(0.1)
    session = build_session(
        cfg, switcher=get_switcher("periodic", m, n_byz=n_byz, K=10),
        grad_fn=grad_fn, params0=params0, sample_batches=sampler, opt=opt,
        sampler_factory=lambda s: make_index_sampler(m, seed=s))
    t0 = time.perf_counter()
    _, logs, evals = session.run(T, eval_fn=eval_fn, eval_every=30)
    run_s = time.perf_counter() - t0
    acc = evals[-1][1]["test_acc"]
    if not acc > 0.8:
        raise AssertionError(f"mode_a: final test accuracy {acc} <= 0.8")

    # one sweep: 8 Periodic(K) cells x 2 seeds as lanes of one program,
    # on a prebuilt scan fn so its second call must hit the jit cache
    ks = (2, 5, 10, 15, 20, 30, 50, 75)
    spec = SweepSpec(
        switchers=tuple(("periodic", dict(n_byz=n_byz, K=k)) for k in ks),
        seeds=(0, 1), scan_fn=make_dynabro_scan_fn(grad_fn, cfg, opt))
    t0 = time.perf_counter()
    session.sweep(spec, T)
    cold_s = time.perf_counter() - t0
    with recompile_guard("mode_a sweep steady state") as guard:
        t0 = time.perf_counter()
        cells = session.sweep(spec, T)
        warm_s = time.perf_counter() - t0
    accs = [eval_fn(p, T)["test_acc"] for cell in cells for p, _ in cell]
    return (f"run T={T}: {run_s:.2f} s, test_acc={acc:.4f}, levels "
            f"{sorted({lg.level for lg in logs})}; sweep {len(ks)} cells x 2 "
            f"seeds: first {cold_s:.2f} s, steady {warm_s:.2f} s, "
            f"{guard.count} recompiles, test_acc min {min(accs):.4f} "
            f"mean {sum(accs) / len(accs):.4f}; "
            f"peak_bytes_in_use={_peak_bytes()}")


def phase_serve() -> str:
    from repro.serve import smoke

    t0 = time.perf_counter()
    if smoke.main() != 0:
        raise AssertionError("serve: the stream failed its checks (above)")
    return (f"16 workers, served carry bitwise == offline driver, "
            f"{time.perf_counter() - t0:.2f} s")


def _train(argv) -> list:
    import math

    from repro.launch import train

    recs = train.main(argv)
    losses = [r["loss"] for r in recs]
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite loss in {losses}")
    return recs


def _describe(recs) -> str:
    first = {}
    for r in recs:
        first.setdefault(r["j"], r["t"])
    steady = [r["seconds"] for r in recs if first[r["j"]] != r["t"]]
    return (f"J={[r['j'] for r in recs]} losses="
            f"{[round(r['loss'], 4) for r in recs]} first-call s per level="
            f"{ {j: round(recs[t]['seconds'], 2) for j, t in first.items()} } "
            f"steady step s={[round(s, 4) for s in steady]}")


def phase_mode_b() -> str:
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_train_step

    one = _MODE_B + ["--mesh", "1x1", "--switch", "static", "--n-byz", "0"]
    level0 = _train(one + ["--steps", "3"])
    mlmc = _train(one + ["--steps", "4", "--mlmc"])
    # the level-0 step just run, lowered again: its aggregation must be the
    # Pallas kernels
    step = build_train_step(get_config("smollm-360m"),
                            make_mesh((1, 1), ("data", "model")),
                            ShapeConfig("cli", 512, 8, "train"),
                            aggregator="cwtm", attack="sign_flip",
                            dtype=jnp.bfloat16)
    _require_compiled_kernel(step.fn.lower(*step.inputs),
                             "mode_b level-0 step")
    return (f"smollm-360m bf16 seq 512 batch 8, 1x1 mesh, tpu_custom_call "
            f"in step; level-0 {_describe(level0)}; mlmc {_describe(mlmc)}; "
            f"peak_bytes_in_use={_peak_bytes()}")


def phase_mode_a_mesh() -> str:
    import numpy as np

    from repro.api import (
        DynaBROConfig, MLMCConfig, get_switcher, make_quadratic_task,
        make_worker_mesh, run_dynabro_scan, sgd,
    )

    # the examples/train_multipod.py demo settings
    T, m = 300, 8
    task = make_quadratic_task()
    cfg = DynaBROConfig(mlmc=MLMCConfig(T=T, m=m, V=3.0, kappa=1.0),
                        aggregator="cwtm", delta=0.3, attack="alie")
    sampler = task.make_sampler(m)

    def run(mesh):
        sw = get_switcher("periodic", m, n_byz=2, K=25)
        t0 = time.perf_counter()
        p, logs, _ = run_dynabro_scan(task.grad_fn, task.params0, sgd(2e-2),
                                      cfg, sw, sampler, T, seed=0, mesh=mesh)
        return np.asarray(p["x"]), logs, time.perf_counter() - t0

    x_sh, logs_sh, s_sh = run(make_worker_mesh(4))
    x_1d, logs_1d, s_1d = run(None)
    np.testing.assert_allclose(x_sh, x_1d, rtol=PARITY_TOL, atol=PARITY_TOL)
    if [lg.failsafe_ok for lg in logs_sh] != [lg.failsafe_ok for lg in logs_1d]:
        raise AssertionError("mode_a_mesh: fail-safe traces differ")
    return (f"T={T} m={m} alie/cwtm: 4-device mesh {s_sh:.2f} s, unsharded "
            f"{s_1d:.2f} s, max|diff|={np.abs(x_sh - x_1d).max():.3g} "
            f"(tolerance {PARITY_TOL}), fail-safe traces identical, "
            f"f(x_T)={task.objective({'x': x_sh}):.6f}")


def phase_mode_b_mesh() -> str:
    # --steps 3 caps MLMC at J=1: one compiled level per mesh
    one = _train(_MODE_B + ["--mesh", "1x1", "--switch", "static",
                            "--n-byz", "0", "--steps", "3", "--mlmc"])
    four = _train(_MODE_B + ["--mesh", "4x1", "--switch", "periodic",
                             "--switch-k", "2", "--n-byz", "1", "--steps", "3",
                             "--mlmc"])
    l1, l4 = one[0]["loss"], four[0]["loss"]
    if abs(l4 - l1) > BF16_LOSS_RTOL * abs(l1):
        raise AssertionError(f"mode_b_mesh: step-0 loss 4x1 {l4} vs 1x1 {l1}")
    return (f"smollm-360m bf16 seq 512, m=4 (1 sign-flipper) cwtm mlmc: "
            f"4x1 {_describe(four)}; 1x1 {_describe(one)}; step-0 loss "
            f"|4x1 - 1x1| = {abs(l4 - l1):.4g} (rtol {BF16_LOSS_RTOL}); "
            f"peak_bytes_in_use(dev0)={_peak_bytes()}")


# ---------------------------------------------------------------- driver


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths, on four chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    n_chips = 4 if args.four_chips else 1
    phases = [("device", lambda: phase_device(n_chips))]
    if args.four_chips:
        phases += [("mode_a_mesh", phase_mode_a_mesh),
                   ("mode_b_mesh", phase_mode_b_mesh)]
    else:
        phases += [("kernels", phase_kernels), ("mode_a", phase_mode_a),
                   ("serve", phase_serve), ("mode_b", phase_mode_b)]
    t_all = time.perf_counter()
    for name, fn in phases:
        t0 = time.perf_counter()
        found = fn()
        print(f"[{name}] {time.perf_counter() - t0:.1f} s | {found}",
              flush=True)
    print(f"[total] {time.perf_counter() - t_all:.1f} s, compile cache "
          f"{cache_dir}", flush=True)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
