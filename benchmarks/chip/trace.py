"""The one reduction from a profiler trace to the numbers every per-layer
metric reads.

``extract(path)`` reads an ``.xplane.pb`` (``jax.profiler.ProfileData``)
into plain lists: per device, the operations of its "XLA Ops" line as
``[name, start_ns, end_ns, kind]``, with ``kind`` one of ``custom`` (a
Pallas kernel: an XLA custom call), ``collective`` or ``compute``; and the
harness's own host spans as ``[name, start_ns, end_ns]``. ``reduce`` turns
that into busy and idle time, per-operation sums, exposed collective time
and the idle gaps of device 0, each named by the host span it fell in.
Both are pure functions of their input, so a recorded extract checks them
(``tests/test_trace.py``).
"""
from __future__ import annotations

import collections

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# the harness's spans (run.py, drivers/): the window and what the host
# does inside it
SPANS = ("window", "place", "dispatch", "wait", "sweep")
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "collective-broadcast")


def op_kind(name: str, category: str) -> str:
    text = f"{category} {name}".lower()
    if "custom-call" in text or "custom_call" in text:
        return "custom"
    if any(c in text for c in COLLECTIVES):
        return "collective"
    return "compute"


def short_name(name: str, category: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...), ...' -> 'fusion.12 (category)'."""
    name = name.split(" = ")[0].lstrip("%")
    return f"{name} ({category})" if category else name


def leaves(ops) -> list:
    """The operations that hold no other: a loop or a conditional is an
    operation of its own in the trace, and so is each operation inside
    it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    inner, stack = set(), []
    for i in order:
        s, e = ops[i][1], ops[i][2]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            inner.add(stack[-1])
        stack.append(i)
    return [op for i, op in enumerate(ops) if i not in inner]


def _stat(event, key: str) -> str:
    for k, v in event.stats:
        if k == key:
            return str(v)
    return ""


def extract(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    category = _stat(ev, "hlo_category")
                    ops.append([short_name(ev.name, category), start,
                                start + int(ev.duration_ns),
                                op_kind(ev.name, category)])
            devices[idx] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        start = int(ev.start_ns)
                        spans.append([ev.name, start,
                                      start + int(ev.duration_ns)])
    return {"devices": devices, "spans": spans}


def union(intervals) -> list:
    """Sorted, merged [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of merged intervals ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def reduce(extracted: dict, n_devices: int, top: int = 10) -> dict:
    """Busy and idle time over the traced window, averaged over the first
    ``n_devices`` devices; op sums, custom-call and exposed collective time
    (over the operations that hold no other) and the idle gaps of device 0.
    Times in seconds."""
    windows = [s for s in extracted["spans"] if s[0] == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one window span, found {len(windows)}")
    lo, hi = windows[0][1], windows[0][2]
    busy, per_op = [], collections.Counter()
    dev0 = None
    for idx in range(n_devices):
        ops = [[n, max(s, lo), min(e, hi), k]
               for n, s, e, k in extracted["devices"].get(idx, [])
               if e > lo and s < hi]
        merged = union([[s, e] for _, s, e, _ in ops])
        busy.append(length(merged))
        if idx == 0:
            dev0 = (ops, merged)
    ops, merged = dev0
    ops = leaves(ops)
    for n, s, e, _ in ops:
        per_op[n] += e - s
    by_kind = collections.Counter()
    for _, s, e, k in ops:
        by_kind[k] += e - s
    coll = union([[s, e] for _, s, e, k in ops if k == "collective"])
    other = union([[s, e] for _, s, e, k in ops if k != "collective"])
    exposed = length(subtract(coll, other))
    gaps = subtract([[lo, hi]], merged)
    host = [s for s in extracted["spans"] if s[0] != "window"]
    named = []
    for gs, ge in gaps:
        best, best_overlap = "untraced", 0
        for name, s, e in host:
            ov = min(e, ge) - max(s, gs)
            if ov > best_overlap:
                best, best_overlap = name, ov
        named.append([best, (ge - gs) * 1e-9])
    named.sort(key=lambda g: -g[1])
    gap_total = collections.Counter()
    for name, sec in named:
        gap_total[name] += sec
    window_s = (hi - lo) * 1e-9
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "busy_s_dev0": busy[0] * 1e-9,
        "custom_s": by_kind["custom"] * 1e-9,
        "collective_s": by_kind["collective"] * 1e-9,
        "collective_exposed_s": exposed * 1e-9,
        "n_ops": len(ops),
        "top_ops": [[n, t * 1e-9] for n, t in per_op.most_common(top)],
        "top_gaps": named[:top],
        "idle_by_span": dict(gap_total),
    }
