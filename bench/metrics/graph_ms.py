"""Whole-graph request latency: the sum of every latency of the window over
the count of requests (closed loop, one client)."""
from bench.metrics import common


def read(ctx):
    return common.mean(ctx, "latency_ms")
