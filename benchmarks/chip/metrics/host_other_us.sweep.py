"""The rest of ``Session.sweep``'s host time per cell-round of the traced
window (us): the duration of the program's ``repro.sweep`` spans less the
self time of its inputs (``repro.schedule``, ``repro.batches``), results
(``repro.results``) and its waits on the device (``repro.wait``): the
dispatches and the glue between them."""
from benchmarks.chip import spans

ACCOUNTED = ("repro.schedule", "repro.batches", "repro.results", "repro.wait")


def read(ctx):
    return spans.sweep_us_per_cell_round(
        ctx, lambda t: t["root_ns"] - sum(t["self_ns"].get(n, 0)
                                          for n in ACCOUNTED))
