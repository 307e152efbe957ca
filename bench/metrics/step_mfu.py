"""The whole step's share of the chip's peak: the model operations the
traced window's decoded tokens and prefilled prompt rows need, over the
window's length times the peak, in percent."""
from bench import counts


def read(ctx):
    t, a = ctx.trace, ctx.traced
    if not t or not t.get("window_s") or not ctx.peak:
        return None
    c = ctx.model
    ops = (counts.decode_flops(c, a["decode_tokens"], a["decode_keys"])
           + counts.prefill_flops(c, a["prefill_rows"], a["prefill_keys"]))
    return 100.0 * ops / (t["window_s"] * ctx.peak["flops_per_s"])
