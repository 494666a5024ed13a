"""Host time building a sweep's inputs per cell-round of the traced window
(us): the self time of the program's ``repro.schedule`` (switchers, mask
schedules, key streams, lane plans, their upload) and ``repro.batches``
(the batch schedule) spans under ``repro.sweep``."""
from benchmarks.chip import spans


def read(ctx):
    return spans.sweep_us_per_cell_round(
        ctx, lambda t: (t["self_ns"].get("repro.schedule", 0)
                        + t["self_ns"].get("repro.batches", 0)))
