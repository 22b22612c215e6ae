"""Device idle share of the traced window: 1 - (union of the intervals in
which an operation ran on the device) / (traced window)."""
from bench.metrics import common


def read(ctx):
    return common.idle_pct(ctx)
