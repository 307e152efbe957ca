"""Mean CPU milliseconds per pump round of the thread that runs the
front-end's pump, waits on the device left out."""
import numpy as np

from bench.metrics._common import host_rounds


def read(ctx):
    v = host_rounds(ctx)
    return 1e3 * float(np.mean(v)) if v else None
