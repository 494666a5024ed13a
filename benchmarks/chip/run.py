#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One process, one cell. It finds the cell in ``BENCHMARK.json``, its
configuration (the ``file`` the configs entry names), its traffic
(``traffic/<traffic>.json``) and its limits (``checks/<cell>.json``), and
hands them to the driver the configuration names (``drivers/<driver>.py``).
The cell's driver builds the program's compiled path and its inputs from the
seed, warms every shape the window uses and drives the first steps, all
counted as set-up; then the window runs for ``--seconds``. With
``--trace 1`` the window runs under the profiler and the cell's per-layer
metrics are read from the trace by ``metrics/<metric>.py``. After the
window the program's state is freed and the cell's plain reference
decides ``correct``; each number compared is printed beside its limit as
the last lines of standard error and, under ``checks``, last in the result.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# this directory holds trace.py: keep it off the path, so that the
# standard library's ``trace`` is not shadowed
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_metric(name: str):
    """A per-layer metric's reader, ``metrics/<name>.py``."""
    mod_name = "benchmarks.chip.metrics." + name.replace(".", "_")
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            mod_name, os.path.join(HERE, "metrics", f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[mod_name] = mod
    return sys.modules[mod_name]


def read_json(rel: str):
    with open(os.path.join(HERE, rel) if not os.path.isabs(rel) else rel) as f:
        return json.load(f)


class NoChip(SystemExit):
    pass


class Run:
    """What one run knows: the cell and its files, the seed, the spans."""

    def __init__(self, bench: dict, cell: dict, config: dict, traffic: dict,
                 limits: dict, seed: int, seconds: float, trace: bool):
        self.bench, self.cell, self.name = bench, cell, cell["name"]
        self.config, self.traffic, self.limits = config, traffic, limits
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.chips = int(cell["chips"])

    @classmethod
    def find(cls, bench: dict, workload: str, seed: int, seconds: float,
             trace: bool) -> "Run":
        """The cell named ``workload`` and the files it names."""
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; known: "
                             f"{sorted(cells)}")
        cell = cells[workload]
        conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
        return cls(bench, cell, read_json(os.path.join(ROOT, conf["file"])),
                   read_json(f"traffic/{cell['traffic']}.json"),
                   read_json(f"checks/{workload}.json")["limits"],
                   seed, seconds, trace)

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span, written into the profiler's trace when it runs."""
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self):
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def require_chip(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {devs}")
    return devs


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def start(run: Run, chip_check: bool = True):
    """JAX with the compile cache in the checkout; the cell's devices."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    # also where JAX was imported before the variable was set (the tests)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no cap: a machine-wide cap evicts the grid's own programs while it
    # writes them, and then every run of it compiles again
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = (require_chip(run.chips) if chip_check
               else jax.devices())[:run.chips]
    return devices


def cache_counter() -> dict:
    """Counts of persistent compile cache hits and misses from here on."""
    from jax import monitoring

    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listen(event, **_):
        if event in names:
            counts[names[event]] += 1

    monitoring.register_event_listener(listen)
    return counts


def make_driver(run: Run, devices):
    return importlib.import_module(
        f"benchmarks.chip.drivers.{run.config['driver']}").Driver(run, devices)


def execute(run: Run, *, chip_check: bool = True, patch=None) -> dict:
    """Set up, measure, trace, check; returns the result object.
    ``patch(driver)``, if given, is applied once the driver has compiled
    its programs (the fault tests break the timed path underneath with
    it)."""
    devices = start(run, chip_check)
    import jax

    from repro.lint.runtime import recompile_guard

    cache = cache_counter()
    t_jax = time.perf_counter() - T_START
    driver = make_driver(run, devices)
    driver.build()
    if patch is not None:
        patch(driver)
    t_build = time.perf_counter() - T_START
    driver.prepare()
    setup_s = time.perf_counter() - T_START
    print(f"[{run.name}] set-up {setup_s:.1f} s: JAX {t_jax:.1f}, build "
          f"{t_build - t_jax:.1f}, prepare {setup_s - t_build:.1f}; "
          f"compile cache hits {cache['hits']}, misses {cache['misses']}",
          file=sys.stderr, flush=True)

    trace_path = None
    if run.trace:
        trace_path = os.path.join(TRACE_DIR, f"{run.name}-{run.seed}")
        shutil.rmtree(trace_path, ignore_errors=True)
        jax.profiler.start_trace(trace_path)
    with recompile_guard(f"{run.name} window", action="count") as guard:
        with run.span("window"):
            t0 = time.perf_counter()
            work = driver.window(t0 + run.seconds)
            window_s = time.perf_counter() - t0
    if run.trace:
        jax.profiler.stop_trace()
    print(f"[{run.name}] window {window_s:.3f} s, compiles in window: "
          f"{guard.count}", file=sys.stderr, flush=True)

    device = devices[0]
    result = {"correct": False, "attempted": work["attempted"],
              "failed": work["failed"], "metrics": {},
              "device": {"platform": device.platform,
                         "kind": device.device_kind, "count": len(devices),
                         "memory_peak_bytes": memory_peak(devices)}}
    if not run.trace:
        for m in run.end_to_end():
            value = (setup_s if m["name"] == "setup_s"
                     else work["amounts"][m["name"]] / window_s)
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from benchmarks.chip import trace as trace_mod

        files = glob.glob(os.path.join(trace_path, "**", "*.xplane.pb"),
                          recursive=True)
        reduced = trace_mod.reduce(trace_mod.extract(files[0]),
                                   n_devices=len(devices))
        shutil.rmtree(trace_path, ignore_errors=True)
        ctx = Layer(run, work, window_s, reduced, driver.facts(work),
                    device.device_kind, len(devices))
        for m in run.per_layer():
            value = load_metric(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["top_gaps"]}

    driver.release()
    gc.collect()
    t_check = time.perf_counter()
    checks = driver.check()
    print(f"[{run.name}] check {time.perf_counter() - t_check:.1f} s",
          file=sys.stderr, flush=True)
    ok = work["failed"] == 0 and all(
        math.isfinite(v) and v <= lim for _, v, lim in checks)
    result["correct"] = bool(ok)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result


class Layer:
    """What a per-layer metric's reader sees."""

    def __init__(self, run, work, window_s, trace, facts, kind, chips):
        self.run, self.work, self.window_s = run, work, window_s
        self.trace, self.facts, self.chips = trace, facts, chips
        from benchmarks.chip import arithmetic

        self.peaks = arithmetic.peaks(kind)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    run = Run.find(read_json(os.path.join(ROOT, "BENCHMARK.json")),
                   args.workload, args.seed, args.seconds, bool(args.trace))
    try:
        result = execute(run)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(f"correct = {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
