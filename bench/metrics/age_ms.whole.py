"""Device milliseconds of the AGE programs per whole-graph request, from the
profiler trace."""
from bench.metrics import common


def read(ctx):
    s = common.age_seconds_per_request(ctx)
    return None if s is None else s * 1e3
