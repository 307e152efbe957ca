"""90th percentile of time to first token over the requests whose first
token fell in the window, timed from when each request was due (an open
loop sends it then), so a stall before admission counts."""
import numpy as np

from bench.metrics._common import in_window


def read(ctx):
    v = [1e3 * (r["times"][0] - r["due"]) for r in ctx.recs.values()
         if r["times"] and in_window(ctx, r["times"][0])]
    return float(np.percentile(v, 90)) if v else None
