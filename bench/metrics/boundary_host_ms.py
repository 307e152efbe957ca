"""Mean milliseconds per round from the end of the round's read-back
(``serve.collect.wait``) to the end of the next ``serve.dispatch``, less
the ``serve.admit`` spans in that stretch: the host's work between one
decode chunk and the next other than admission."""
from bench import spans


def read(ctx):
    return spans.boundary_host_ms(spans.of(ctx))
