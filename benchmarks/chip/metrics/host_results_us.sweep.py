"""Host time returning a sweep's results per cell-round of the traced
window (us): the self time of the program's ``repro.results`` spans (the
per-lane scatter of the final carry and the per-round logs) under
``repro.sweep``."""
from benchmarks.chip import spans


def read(ctx):
    return spans.sweep_us_per_cell_round(
        ctx, lambda t: t["self_ns"].get("repro.results", 0))
