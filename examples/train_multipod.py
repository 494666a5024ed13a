"""End-to-end distributed DynaBRO on a (simulated) mesh — both halves.

1. **Mode B** (production scale-out): trains a reduced llama-family model
   with FSDP + tensor parallelism and the robust all-to-all aggregation, one
   Byzantine worker sign-flipping, full MLMC levels and the fail-safe filter
   — the production path of ``repro.launch.train``.
2. **Mode A, sharded compiled driver** (DESIGN.md §7): the whole T-round
   Algorithm-2 loop compiled under a fully-manual ``shard_map``, the m
   simulated workers laid out across a 4-device ``workers`` mesh, checked
   against the single-device ``run_dynabro_scan`` within 1e-6.

Both run on CPU with forced host devices:

  PYTHONPATH=src python examples/train_multipod.py            # both demos
  PYTHONPATH=src python examples/train_multipod.py --mode b   # Mode B only
"""
import argparse
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")

SHARDED_SCAN_DEMO = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import time
import jax, numpy as np
from repro.core.mlmc import MLMCConfig
from repro.core.robust_train import DynaBROConfig, run_dynabro_scan
from repro.core.scenarios import make_quadratic_task
from repro.core.switching import get_switcher
from repro.launch.mesh import make_worker_mesh
from repro.optim.optimizers import sgd

T, m = 300, 8
task = make_quadratic_task()
cfg = DynaBROConfig(mlmc=MLMCConfig(T=T, m=m, V=3.0, kappa=1.0),
                    aggregator="cwtm", delta=0.3, attack="alie")
sw = lambda: get_switcher("periodic", m, n_byz=2, K=25)
sampler = task.make_sampler(m)
mesh = make_worker_mesh(4)
print(f"mesh={mesh.shape} workers(m)={m} T={T} attack=alie agg=cwtm")
t0 = time.time()
p_sh, logs, _ = run_dynabro_scan(task.grad_fn, task.params0, sgd(2e-2), cfg,
                                 sw(), sampler, T, seed=0, mesh=mesh)
print(f"sharded scan: f(x_T)={task.objective(p_sh):.5f} "
      f"({time.time()-t0:.1f}s, {sum(l.cost for l in logs)} grad evals/worker)")
p_1d, _, _ = run_dynabro_scan(task.grad_fn, task.params0, sgd(2e-2), cfg,
                              sw(), sampler, T, seed=0)
# not bitwise across devices: the all-gather is a fusion boundary and XLA
# contracts the gradient's multiply-adds differently around it (DESIGN.md §7)
diff = float(np.abs(np.asarray(p_sh["x"]) - np.asarray(p_1d["x"])).max())
print(f"max |sharded - single-device| = {diff:.3g} (tolerance 1e-6)")
np.testing.assert_allclose(np.asarray(p_sh["x"]), np.asarray(p_1d["x"]),
                           rtol=1e-6, atol=1e-6)
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="both", choices=["a", "b", "both"])
    args = ap.parse_args()
    env = dict(os.environ)
    # prepend (don't clobber): a pip-installed repro works without this, and
    # an existing PYTHONPATH keeps working with it
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(ROOT, "src"), env.get("PYTHONPATH")) if p)
    rc = 0
    if args.mode in ("a", "both"):
        print("== Mode A: sharded compiled driver (4-device workers mesh) ==")
        rc = subprocess.call([sys.executable, "-c", SHARDED_SCAN_DEMO],
                             env=env, cwd=ROOT)
        if rc:
            sys.exit(rc)
    if args.mode in ("b", "both"):
        print("== Mode B: FSDP + tensor-parallel robust training ==")
        cmd = [sys.executable, "-m", "repro.launch.train",
               "--arch", "qwen3-0.6b", "--reduced",
               "--devices", "8", "--mesh", "2x2x2",  # pod x data x model
               "--steps", "30", "--global-batch", "8", "--seq-len", "128",
               "--mlmc", "--aggregator", "cwmed", "--attack", "sign_flip",
               "--switch", "periodic", "--switch-k", "5", "--n-byz", "1",
               "--ckpt-every", "15"]
        print("+", " ".join(cmd))
        rc = subprocess.call(cmd, env=env, cwd=ROOT)
    sys.exit(rc)


if __name__ == "__main__":
    main()
