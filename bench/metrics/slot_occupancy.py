"""Live slots over slots per decode step in the window, in percent:
tokens emitted over decode steps times slots (a live slot emits one
token per step)."""


def read(ctx):
    w, sv = ctx.window, ctx.serve
    steps = len(w["rounds"]) * sv["decode_chunk"]
    return 100.0 * w["tokens"] / (steps * sv["max_batch"])
