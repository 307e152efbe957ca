"""Mean milliseconds per admission attempt of host work the device
waits for: time in ``serve.admit``, outside the prefix tracker's
``serve.prefix_count``, in which the device runs no program (lookup,
allocation, block tables, the dispatches of the prefill chunks and of
the slot's state updates).  Time hidden behind the admission's own
prefill, or behind a decode chunk in flight, is not in it."""
from bench import spans


def read(ctx):
    return spans.admit_host_ms(spans.of(ctx))
