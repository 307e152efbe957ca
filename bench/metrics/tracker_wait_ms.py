"""Mean milliseconds per admission attempt in the prefix tracker's
``serve.prefix_count``: the count-min hashing, accumulate and query, about
66 single-op programs each dispatched on its own, and the read-back.  On
a v5e the host's dispatch of those programs takes the time, with the
device idle; no wait on the read-back was seen.  Behind programs still
in flight (another admission's prefill) the dispatches would wait too:
the runtime holds at most 32 in flight."""
from bench import spans


def read(ctx):
    return spans.tracker_wait_ms(spans.of(ctx))
