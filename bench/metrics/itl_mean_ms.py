"""Mean of all gaps between consecutive streamed tokens of a request
whose later token fell in the window.  Tokens that arrive together (a
decode chunk's) count as the gaps they are, near 0."""
import numpy as np

from bench.metrics._common import in_window


def read(ctx):
    v = []
    for r in ctx.recs.values():
        t = r["times"]
        v.extend(1e3 * (b - a) for a, b in zip(t, t[1:])
                 if in_window(ctx, b))
    return float(np.mean(v)) if v else None
