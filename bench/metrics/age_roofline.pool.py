"""Share of the AGE's roofline in a GraphSAGE-pool cell: the compulsory time
of its max passes at the chip's peaks (``counts_pool.age_work``) over the AGE
programs' device time per request. Which of the two bounds it is printed on
standard error."""
import sys

from bench import counts_pool
from bench.metrics import common


def read(ctx):
    if not ctx["peaks"]:
        return None
    s = common.age_seconds_per_request(ctx)
    w = counts_pool.request_work(ctx, "age")
    if s is None or w is None:
        return None
    print(f"age_roofline.pool: {w.bound(ctx['peaks'])}-bound, compulsory "
          f"{w.seconds(ctx['peaks']):.9f} s over {s:.9f} s", file=sys.stderr)
    return 100.0 * w.seconds(ctx["peaks"]) / s
