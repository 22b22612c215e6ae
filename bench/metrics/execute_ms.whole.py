"""Mean ``run_ms`` (the program's host clock around the forward, ending in
``block_until_ready``) of the window's whole-graph requests."""
from bench.metrics import common


def read(ctx):
    return common.mean(ctx, "run_ms")
