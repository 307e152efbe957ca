"""Device milliseconds per call of the prefill-chunk program (plain or
sketched; the draft's own is left out), from the trace's module times."""
from bench import devtrace

# the programs ``SlotScheduler`` names ``prefill_chunk`` and
# ``sketched_prefill_chunk``, not ``draft_prefill_chunk``
MODULE = r"^jit_(sketched_)?prefill_chunk\b"


def read(ctx):
    if not ctx.trace:
        return None
    m = devtrace.module_time(ctx.trace, MODULE)
    if m["n"] == 0:
        return None
    return 1e3 * m["s"] / m["n"]
