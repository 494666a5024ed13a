"""The program's host spans, reduced to self times.

The program records its host spans (``repro.obs``) while a profiler
session runs: ``(name, start_ns, end_ns, parent, counts)``, ``parent``
the index of the enclosing span in the same list, ``None`` for a root.
``self_times`` keeps the trees under the roots named ``root`` and sums, per
span name, each span's duration less what its children cover (what the
host did in that span itself). It is a pure function of the records, so
hand-made records check it (``tests/test_spans.py``), and the arithmetic
lives here, with the benchmark, and not in the program.
"""
from __future__ import annotations

import collections


def program_records():
    """The records of the program's spans, or ``None`` where the program
    has no ``repro.obs`` (a commit from before it)."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.records()


def self_times(records, root: str = "repro.sweep"):
    """``{"roots": n, "root_ns": summed duration of the roots, "self_ns":
    {name: summed self time}}`` over the trees under the spans named
    ``root`` that have no parent; ``None`` when there is no such root."""
    if not records:
        return None
    children = collections.defaultdict(list)
    for i, (_, _, _, parent, _) in enumerate(records):
        if parent is not None:
            children[parent].append(i)
    roots = [i for i, (name, _, _, parent, _) in enumerate(records)
             if name == root and parent is None]
    if not roots:
        return None
    self_ns = collections.Counter()
    stack = list(roots)
    while stack:
        i = stack.pop()
        name, start, end, _, _ = records[i]
        covered = sum(records[c][2] - records[c][1] for c in children[i])
        self_ns[name] += (end - start) - covered
        stack.extend(children[i])
    return {"roots": len(roots),
            "root_ns": sum(records[i][2] - records[i][1] for i in roots),
            "self_ns": dict(self_ns)}


def sweep_us_per_cell_round(ctx, part):
    """``part(times)`` in ns, over the window's cell-rounds, in us; ``None``
    when the window recorded no ``repro.sweep`` span."""
    times = self_times(program_records())
    if times is None:
        return None
    rounds = ctx.work["amounts"]["cell_rounds_per_s"]
    return 1e-3 * part(times) / rounds
