"""End-to-end DynaBRO training driver (Mode B).

Runs Algorithm 2 on a real device mesh: per round, sample J ~ Geom(1/2)
host-side, dispatch to the per-level compiled step (lowered lazily, cached),
feed per-worker synthetic LM batches, update the Byzantine mask from the
switching strategy, checkpoint periodically.

On a CPU host, ``--devices N`` spawns N placeholder devices (the flag is
applied before JAX initializes, by re-exec); on an accelerator the mesh is
built from the attached devices. Example:

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \\
      --devices 8 --mesh 4x2 --steps 50 --reduced --attack sign_flip \\
      --aggregator cwtm --switch periodic --switch-k 10
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _reexec_with_devices(n: int, argv):
    """CPU only: re-exec with ``n`` placeholder host devices (the flag must
    be set before JAX initializes)."""
    if os.environ.get("_REPRO_DEVICES_SET") == str(n):
        return
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count={n}").strip()
    os.environ["_REPRO_DEVICES_SET"] = str(n)
    os.execv(sys.executable,
             [sys.executable, "-m", "repro.launch.train", *argv])


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU only: placeholder host devices (re-execs)")
    ap.add_argument("--mesh", default="", help="e.g. 4x2 (data x model)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "momentum", "adam", "adagrad_norm"])
    ap.add_argument("--aggregator", default="cwmed")
    ap.add_argument("--attack", default="none")
    ap.add_argument("--delta", type=float, default=0.25)
    ap.add_argument("--switch", default="static",
                    choices=["static", "periodic", "bernoulli", "momentum_tailored"])
    ap.add_argument("--switch-k", type=int, default=10)
    ap.add_argument("--n-byz", type=int, default=1)
    ap.add_argument("--mlmc", action="store_true", help="full MLMC levels")
    ap.add_argument("--V", type=float, default=8.0)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None) -> list:
    """Run the CLI on ``argv`` (default: the command line). Returns one
    record per step: ``{"t", "j", "n_byz", "loss", "seconds"}``, plus
    ``"failsafe_ok"`` and ``"corr_norm"`` on MLMC levels J ≥ 1; ``seconds``
    is the step's wall time up to its results being ready (the first call
    of each level includes compilation)."""
    argv = sys.argv[1:] if argv is None else list(argv)
    ap = _parser()
    args = ap.parse_args(argv)
    if args.steps < 1:
        ap.error("--steps must be at least 1")

    if args.devices:
        _reexec_with_devices(args.devices, argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import save_checkpoint
    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeConfig
    from repro.core.mlmc import MLMCConfig, sample_level
    from repro.core.switching import get_switcher
    from repro.data import SyntheticLMData
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_mlmc_train_step, build_train_step
    from repro.models import init_params
    from repro.optim.optimizers import get_optimizer

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_mesh(dims, ("pod", "data", "model")[-len(dims):])
    else:
        mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    m = 1
    for a in mesh.axis_names:
        if a != "model":
            m *= mesh.shape[a]
    print(f"mesh={dict(mesh.shape)} workers(m)={m} arch={cfg.arch_id} "
          f"params={cfg.param_count()/1e6:.1f}M")

    shape = ShapeConfig("cli", args.seq_len, args.global_batch, "train")
    opt = get_optimizer(args.optimizer, args.lr)
    mlmc_cfg = MLMCConfig(T=args.steps, m=m, V=args.V, option=1, kappa=1.0,
                          j_cap=3)
    sw_kw = {"static": {"n_byz": args.n_byz},
             "periodic": {"n_byz": args.n_byz, "K": args.switch_k},
             "bernoulli": {"p": 0.02, "D": args.switch_k, "delta_max": 0.45},
             "momentum_tailored": {"alpha": 0.1}}[args.switch]
    switcher = get_switcher(args.switch, m, seed=args.seed, **sw_kw)
    data = SyntheticLMData(cfg.vocab_size, args.seq_len, args.global_batch,
                           seed=args.seed)

    dtype = jnp.float32 if args.reduced else jnp.bfloat16
    steps_cache = {}

    def get_step(j):
        if j not in steps_cache:
            if j == 0:
                steps_cache[j] = build_train_step(
                    cfg, mesh, shape, aggregator=args.aggregator,
                    attack=args.attack, lr=args.lr, delta=args.delta, opt=opt,
                    dtype=dtype)
            else:
                steps_cache[j] = build_mlmc_train_step(
                    cfg, mesh, shape, mlmc_cfg, j, aggregator=args.aggregator,
                    attack=args.attack, delta=args.delta, opt=opt, dtype=dtype)
        return steps_cache[j]

    def place(tree, like):
        return jax.tree.map(lambda x, s: jax.device_put(x, s.sharding), tree, like)

    def shardings(like):
        return jax.tree.map(lambda s: s.sharding, like)

    rng = np.random.default_rng(args.seed)
    levels = [min(sample_level(rng, mlmc_cfg.j_max), mlmc_cfg.j_max)
              if args.mlmc else 0 for _ in range(args.steps)]
    first = get_step(levels[0])
    with jax.set_mesh(mesh):
        # initial state is created directly in the step's layout, never
        # whole on one device first
        params = jax.jit(lambda k: init_params(cfg, k, dtype=dtype),
                         out_shardings=shardings(first.inputs[0]))(
            jax.random.PRNGKey(args.seed))
        opt_state = jax.jit(opt.init, out_shardings=shardings(
            first.inputs[1]))(params)
        records = []
        t_start = time.time()
        for t, j in enumerate(levels):
            step = get_step(j)
            batch = place(data.batch(t, args.global_batch * 2 ** j),
                          step.inputs[2])
            maskf = place(jnp.asarray(switcher.mask(t), jnp.float32),
                          step.inputs[3])
            t0 = time.perf_counter()
            params, opt_state, out = step.fn(params, opt_state, batch, maskf)
            out = jax.block_until_ready(out)
            rec = {"t": t, "j": j, "n_byz": int(maskf.sum()),
                   "seconds": time.perf_counter() - t0}
            if j > 0:
                loss, ok, dn = out
                rec.update(failsafe_ok=float(ok), corr_norm=float(dn))
                msg = (f"J={j} loss={float(loss):.4f} failsafe_ok={float(ok):.0f} "
                       f"|ĝJ-ĝJ-1|={float(dn):.3f}")
            else:
                loss = out
                msg = f"loss={float(loss):.4f}"
            rec["loss"] = float(loss)
            records.append(rec)
            if t % max(1, args.steps // 20) == 0 or t == args.steps - 1:
                print(f"step {t:5d} byz={rec['n_byz']}/{m} {msg} "
                      f"({time.time()-t_start:.1f}s)")
            if args.ckpt_every and (t + 1) % args.ckpt_every == 0:
                save_checkpoint(os.path.join(args.ckpt_dir,
                                             f"{cfg.arch_id}_step{t+1}"),
                                params, step=t + 1)
    print("done in", round(time.time() - t_start, 1), "s")
    return records


if __name__ == "__main__":
    main()
