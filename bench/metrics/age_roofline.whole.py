"""Share of the AGE's roofline: its compulsory time at the chip's peaks
(``counts.age_work``) over its device time per request. Which of the two
bounds it is printed on standard error."""
import sys

from bench.metrics import common


def read(ctx):
    if not ctx["peaks"]:
        return None
    s = common.age_seconds_per_request(ctx)
    w = common.request_work(ctx, "age")
    if s is None or w is None:
        return None
    print(f"age_roofline.whole: {w.bound(ctx['peaks'])}-bound, compulsory "
          f"{w.seconds(ctx['peaks']):.9f} s over {s:.9f} s", file=sys.stderr)
    return 100.0 * w.seconds(ctx["peaks"]) / s
