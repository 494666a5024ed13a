"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh).

MUST run as its own process: the first two lines pin 512 placeholder devices
before any other import (JAX locks the device count on first init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all        # orchestrates subprocesses

Each run writes experiments/dryrun/<arch>__<shape>__<mesh>.json with
cost_analysis, memory_analysis and the parsed per-device collective bytes —
the inputs to the §Roofline report (benchmarks/roofline.py).
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import build_step  # noqa: E402

OUT_DIR = os.environ.get("REPRO_DRYRUN_OUT") or os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(txt: str) -> int:
    total = 0
    for dt, dims, in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_RE2 = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _group_size(line: str) -> int:
    m = _GROUP_RE.search(line)
    if m:  # iota format [n_groups, group_size]<=[...]
        return int(m.group(2))
    m = _GROUP_RE2.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


_COLL_RE = re.compile(
    r"=\s+(\(?[\w\[\],{}\s/*+=]*?\)?)\s+(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(-start)?\(")
_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+)(?:\.v\d+)? \(.*\) -> .* \{\s*$")
_WHILE_RE = re.compile(r"while\(.*?condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_LIMIT_RE = re.compile(r"s32\[\] constant\((\d+)\)")


def _split_computations(hlo: str) -> dict:
    """name -> list of body lines."""
    comps, cur, name = {}, None, None
    for line in hlo.splitlines():
        m = _COMP_RE.match(line)
        if m:
            name = m.group(1)
            cur = []
            comps[name] = cur
            continue
        if line.startswith("}"):
            name, cur = None, None
            continue
        if cur is not None:
            cur.append(line.strip())
    return comps


def parse_collectives(hlo: str) -> dict:
    """Per-device collective bytes by kind, weighted by while-loop (lax.scan)
    trip counts (parsed from each loop condition's comparison constant).
    Roofline convention: ring algorithms move size*(n-1)/n per device,
    all-reduce moves 2x that."""
    comps = _split_computations(hlo)
    # trip counts: condition computation -> limit constant (max s32 constant)
    cond_limit = {}
    for name, lines in comps.items():
        consts = [int(x) for l in lines for x in _LIMIT_RE.findall(l)]
        if consts:
            cond_limit[name] = max(consts)

    def line_bytes(s):
        m = _COLL_RE.search(s)
        if not m:
            return None
        shape_txt, op = m.group(1), m.group(2)
        if m.group(3) is None and (op + "-done(") in s:
            return None
        nbytes = _shape_bytes(shape_txt)
        n = _group_size(s)
        frac = (n - 1) / max(n, 1)
        if op == "all-reduce":
            moved = 2.0 * nbytes * frac
        elif op == "all-gather":
            moved = nbytes * frac  # output-sized
        elif op == "reduce-scatter":
            moved = float(nbytes)  # input-sized reduced tensor moves (n-1)/n*in
        elif op == "all-to-all":
            moved = nbytes * frac
        else:
            moved = float(nbytes)
        return op, moved

    import functools as _ft

    @_ft.lru_cache(maxsize=None)
    def comp_totals(name):
        out = {k: 0.0 for k in _COLLECTIVES}
        counts = {k: 0.0 for k in _COLLECTIVES}
        for s in comps.get(name, ()):
            lb = line_bytes(s)
            if lb and "-done(" not in s.split("(")[0]:
                op, moved = lb
                out[op] += moved
                counts[op] += 1
            w = _WHILE_RE.search(s)
            if w:
                cond, body = w.group(1), w.group(2)
                trip = cond_limit.get(cond, 1)
                sub_out, sub_counts = comp_totals(body)
                for k in _COLLECTIVES:
                    out[k] += trip * sub_out[k]
                    counts[k] += trip * sub_counts[k]
        return out, counts

    entry = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = re.match(r"ENTRY %?([\w.\-]+)", line)
            if m:
                entry = m.group(1)
            break
    if entry is None or entry not in comps:
        # fall back: flat sum over all computations
        entry_totals = [comp_totals(n) for n in comps]
        out = {k: sum(t[0][k] for t in entry_totals) for k in _COLLECTIVES}
        counts = {k: sum(t[1][k] for t in entry_totals) for k in _COLLECTIVES}
    else:
        out, counts = comp_totals(entry)
    return {"bytes": out, "counts": counts, "total_bytes": sum(out.values())}


def run_one(arch: str, shape_name: str, multi_pod: bool,
            aggregator: str = "cwmed", attack: str = "none",
            level: int = 0, out_dir: str = OUT_DIR, tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not cfg.supports_shape(shape):
        rec = {"arch": arch, "shape": shape_name, "skipped": True,
               "reason": "unsupported (see DESIGN.md §Arch-applicability)"}
        _write(rec, arch, shape_name, multi_pod, out_dir, tag)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    built = build_step(cfg, mesh, shape, aggregator=aggregator, attack=attack,
                       level=level) if shape.kind == "train" else \
        build_step(cfg, mesh, shape)
    with jax.set_mesh(mesh):
        lowered = built.fn.lower(*built.inputs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    mem = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        mem[f] = getattr(ma, f, None)
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    weighted = parse_weighted_costs(hlo)
    _save_hlo(hlo, arch, shape_name, multi_pod, out_dir, tag)
    rec = {
        "arch": arch, "shape": shape_name,
        "weighted": weighted,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "level": level,
        "aggregator": aggregator if shape.kind == "train" else None,
        "flops": ca.get("flops"), "bytes_accessed": ca.get("bytes accessed"),
        "transcendentals": ca.get("transcendentals"),
        "memory": mem, "collectives": coll,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        "t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
        "hlo_bytes": len(hlo),
        "step_name": built.name,
    }
    _write(rec, arch, shape_name, multi_pod, out_dir, tag)
    return rec


def _save_hlo(hlo: str, arch, shape_name, multi_pod, out_dir, tag=""):
    import gzip
    os.makedirs(out_dir, exist_ok=True)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_tag}{suffix}.hlo.gz")
    with gzip.open(path, "wt") as f:
        f.write(hlo)


def _write(rec, arch, shape_name, multi_pod, out_dir, tag=""):
    os.makedirs(out_dir, exist_ok=True)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_tag}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[dryrun] wrote {path}")


def orchestrate(jobs, parallel: int = 4, extra_args=()):
    """Run each (arch, shape, multi_pod) in its own subprocess."""
    procs = []
    results = {}

    def launch(job):
        arch, shape, mp = job
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape] + (["--multi-pod"] if mp else []) + list(extra_args)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "..")
        return job, subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                     stderr=subprocess.STDOUT, text=True)

    queue = list(jobs)
    running = []
    while queue or running:
        while queue and len(running) < parallel:
            running.append(launch(queue.pop(0)))
        done = []
        for i, (job, p) in enumerate(running):
            if p.poll() is not None:
                out = p.stdout.read()
                ok = p.returncode == 0
                results[job] = ok
                status = "OK" if ok else "FAIL"
                print(f"[{status}] {job}")
                if not ok:
                    print(out[-3000:])
                done.append(i)
        for i in reversed(done):
            running.pop(i)
        time.sleep(1.0)
    n_ok = sum(results.values())
    print(f"\n{n_ok}/{len(results)} dry-runs succeeded")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS + ["all"], default=None)
    ap.add_argument("--shape", choices=list(SHAPES) + ["all"], default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="all 40 pairs, both meshes")
    ap.add_argument("--aggregator", default="cwmed")
    ap.add_argument("--attack", default="none")
    ap.add_argument("--level", type=int, default=0)
    ap.add_argument("--parallel", type=int, default=4)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    if args.all:
        jobs = [(a, s, mp) for a in ARCH_IDS for s in SHAPES
                for mp in (False, True)]
        orchestrate(jobs, parallel=args.parallel)
        return
    archs = ARCH_IDS if args.arch in (None, "all") else [args.arch]
    shapes = list(SHAPES) if args.shape in (None, "all") else [args.shape]
    if len(archs) * len(shapes) > 1:
        orchestrate([(a, s, args.multi_pod) for a in archs for s in shapes],
                    parallel=args.parallel)
        return
    rec = run_one(archs[0], shapes[0], args.multi_pod, aggregator=args.aggregator,
                  attack=args.attack, level=args.level, tag=args.tag)
    if not rec.get("skipped"):
        print(json.dumps({k: rec[k] for k in
                          ("flops", "bytes_accessed", "memory", "collectives",
                           "t_compile_s")}, indent=1, default=str))




# ---------------------------------------------------------------- weighted costs

_DOT_RE = re.compile(
    r"%?([\w.\-]+) = (\S+) dot\(%?([\w.\-]+),? %?([\w.\-]+)\), .*?"
    r"lhs_contracting_dims=\{([\d,]*)\}")
_DEF_RE = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = (\(?[\w\[\],{}\s/*]+?\)?) ")


def _dims_of(type_txt: str):
    m = _SHAPE_RE.search(type_txt)
    if not m:
        return None
    return [int(x) for x in m.group(2).split(",") if x]


def parse_weighted_costs(hlo: str) -> dict:
    """Trip-weighted per-device FLOPs (dot ops) and materialized bytes
    (fusion/dot/copy/conv outputs+operands), from the optimized HLO.

    XLA's compiled.cost_analysis() counts each while (lax.scan) body ONCE;
    this analyzer multiplies by the loop trip count parsed from each loop
    condition, giving the true per-step cost for scan-over-layers models.
    """
    comps = _split_computations(hlo)
    cond_limit = {}
    for name, lines in comps.items():
        consts = [int(x) for l in lines for x in _LIMIT_RE.findall(l)]
        if consts:
            cond_limit[name] = max(consts)

    BYTES_OPS = ("fusion(", "dot(", "convolution(", "copy(", "dynamic-slice(",
                 "dynamic-update-slice(", "sort(", "reduce(", "transpose(",
                 "all-gather(", "all-to-all(", "broadcast(", "concatenate(")

    import functools as _ft

    @_ft.lru_cache(maxsize=None)
    def comp_cost(name):
        flops = 0.0
        byts = 0.0
        shapes = {}
        lines = comps.get(name, ())
        for s in lines:
            dm = _DEF_RE.match(s)
            if dm:
                shapes[dm.group(1)] = dm.group(2)
        for s in lines:
            dm = _DEF_RE.match(s)
            out_type = dm.group(2) if dm else ""
            mdot = _DOT_RE.search(s)
            if mdot:
                out_dims = _dims_of(mdot.group(2)) or []
                lhs_type = shapes.get(mdot.group(3).rstrip(","), "")
                lhs_dims = _dims_of(lhs_type)
                cdims = [int(x) for x in mdot.group(5).split(",") if x]
                k = 1
                if lhs_dims:
                    for c in cdims:
                        if c < len(lhs_dims):
                            k *= lhs_dims[c]
                n = 1
                for d in out_dims:
                    n *= d
                flops += 2.0 * n * k
            if any(op in s for op in BYTES_OPS) and " = " in s:
                byts += _shape_bytes(out_type)
                for opn in re.findall(r"%([\w.\-]+)", s.split("(", 1)[1] if "(" in s else ""):
                    if opn in shapes:
                        byts += _shape_bytes(shapes[opn])
            w = _WHILE_RE.search(s)
            if w:
                trip = cond_limit.get(w.group(1), 1)
                f2, b2 = comp_cost(w.group(2))
                flops += trip * f2
                byts += trip * b2
            # fusion calls reference a computation: calls=%fused_x
            fc = re.search(r"(?:calls|to_apply)=%?([\w.\-]+)", s)
            if fc and fc.group(1) in comps and "while(" not in s:
                f2, b2 = comp_cost(fc.group(1))
                flops += f2  # fusion bodies contain dots on CPU sometimes
                byts += 0.0  # avoid double-counting buffer traffic
        return flops, byts

    entry = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = re.match(r"ENTRY %?([\w.\-]+)", line)
            if m:
                entry = m.group(1)
            break
    if entry and entry in comps:
        f, b = comp_cost(entry)
    else:
        f = b = 0.0
    return {"flops_weighted": f, "bytes_weighted": b}


if __name__ == "__main__":
    main()
