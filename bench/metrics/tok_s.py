"""Output tokens delivered per second over the window's whole rounds."""


def read(ctx):
    w = ctx.window
    return w["tokens"] / (w["t_b"] - w["t_a"])
