"""90th percentile of the wait from a request's due time to its
admission into a slot, over the requests admitted in the window."""
import numpy as np

from bench.metrics._common import in_window


def read(ctx):
    adm = ctx.rec.admitted
    v = [1e3 * (adm[rid] - r["due"]) for rid, r in ctx.recs.items()
         if rid in adm and in_window(ctx, adm[rid])]
    return float(np.percentile(v, 90)) if v else None
