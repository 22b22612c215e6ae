"""A GraphSAGE-pool request's share of the chip's peak: the compulsory time
of the whole forward (``counts_pool.forward_work``: operations at their peaks
or bytes at HBM bandwidth, the larger) over the mean request latency of the
window."""
import sys

from bench import counts_pool
from bench.metrics import common


def read(ctx):
    if not ctx["peaks"]:
        return None
    w = counts_pool.request_work(ctx, "forward")
    lat = common.mean(ctx, "latency_ms")
    if w is None or not lat:
        return None
    print(f"mfu.pool: {w.bound(ctx['peaks'])}-bound, compulsory "
          f"{w.seconds(ctx['peaks']):.9f} s over {lat / 1e3:.9f} s", file=sys.stderr)
    return 100.0 * w.seconds(ctx["peaks"]) / (lat / 1e3)
