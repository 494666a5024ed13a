"""Serve smoke test — ``PYTHONPATH=src python -m repro.serve.smoke``.

Launches the aggregation server on the 2D quadratic testbed with 16
simulated workers, pushes a few hundred updates through the ring, polls the
HTTP health endpoint until the stream completes, asserts the served carry is
bitwise-identical to the offline compiled driver, and shuts down cleanly.
Exit code 0 on success; this is the CI ``serve-smoke`` step.
"""
from __future__ import annotations

import json
import sys
import tempfile
import time
import urllib.request

import numpy as np

from repro.api import build_session
from repro.core.mlmc import MLMCConfig
from repro.core.robust_train import DynaBROConfig
from repro.core.scenarios import make_quadratic_task
from repro.core.switching import get_switcher
from repro.optim.optimizers import adagrad_norm
from repro.serve import AggregationServer, ServeConfig, SimulatedWorkers
from repro.serve.client import worker_payloads

M, T, SEED = 16, 32, 7


def main() -> int:
    task = make_quadratic_task()
    cfg = DynaBROConfig(mlmc=MLMCConfig(T=T, m=M, V=3.0, kappa=1.0, j_cap=2),
                        aggregator="cwmed", delta=0.4, attack="sign_flip")
    switcher = get_switcher("periodic", M, n_byz=4, K=5, seed=SEED)

    def session():
        return build_session(cfg, task, switcher=switcher,
                             opt=adagrad_norm(2e-2), seed=SEED)

    # offline reference: the whole-T compiled driver on the same session
    params_ref, logs_ref, _ = session().run(T)

    sess = session()
    with tempfile.NamedTemporaryFile(mode="r", suffix=".jsonl") as logf:
        server = AggregationServer(sess, T, ServeConfig(
            capacity=256, lookahead_rounds=4, health_port=0,
            metrics_log=logf.name))
        server.start()
        workers = SimulatedWorkers(
            server, worker_payloads(sess, T), jitter_s=0.002).start()
        url = server.health.url

        deadline = time.monotonic() + 120.0
        health = {}
        while time.monotonic() < deadline:
            with urllib.request.urlopen(url + "/health", timeout=5) as r:
                health = json.load(r)
            assert health["status"] in ("live", "draining", "completed"), health
            if health["round"] >= T:
                break
            time.sleep(0.05)
        assert health.get("round") == T, f"stream stalled: {health}"
        assert health["rounds_completed"] == T, health
        assert health["updates_accepted"] == M * T, health

        if not workers.join(timeout=30.0) or workers.failures:
            print(f"worker failures: {workers.failures}", file=sys.stderr)
            return 1
        server.stop(drain=True)
        snap = server.snapshot()
        events = [json.loads(ln) for ln in logf.readlines() if ln.strip()]
        server.close()

    if server.error is not None:
        print(f"server error: {server.error!r}", file=sys.stderr)
        return 1
    got, want = np.asarray(server.params["x"]), np.asarray(params_ref["x"])
    if not np.array_equal(got, want):
        print(f"served carry != offline driver: max |diff| = "
              f"{np.abs(got - want).max():.3g} ({got} vs {want})",
              file=sys.stderr)
        return 1
    assert [(lg.level, lg.failsafe_ok) for lg in server.logs] == \
           [(lg.level, lg.failsafe_ok) for lg in logs_ref]
    assert sum(1 for e in events if e.get("event") == "round") == T
    print(f"serve smoke OK: {T} rounds x {M} workers bitwise == offline "
          f"driver; {snap['updates_per_sec']:.0f} updates/s, ring high-water "
          f"{snap['ring_high_water']}/{snap['ring_capacity']}, staleness "
          f"mean {snap['staleness_mean_s'] * 1e3:.2f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
