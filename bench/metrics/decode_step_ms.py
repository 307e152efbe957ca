"""Device milliseconds per decode step: the decode-chunk program's time
in the trace over the steps it ran."""
from bench import devtrace


def read(ctx):
    if not ctx.trace:
        return None
    m = devtrace.module_time(ctx.trace, r"chunk_fn")
    if m["n"] == 0:
        return None
    return 1e3 * m["s"] / (m["n"] * ctx.serve["decode_chunk"])
