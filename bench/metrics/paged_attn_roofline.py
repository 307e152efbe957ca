"""The paged-attention kernel's share of its roofline: the least time
the chip needs for the attention the traced window's decode steps and
prefill chunks require (the larger of operations over peak operations
and bytes over peak bandwidth), over the kernel's time in the trace, in
percent."""
from bench import counts, devtrace


def read(ctx):
    t, a = ctx.trace, ctx.traced
    if not t or not ctx.peak:
        return None
    secs = devtrace.time_of(t, "paged_attention")
    if secs <= 0:
        return None
    c = ctx.model
    ops = counts.attn_flops(c, a["decode_keys"] + a["prefill_keys"])
    moved = (counts.attn_bytes(c, a["decode_keys"], a["decode_tokens"])
             + counts.attn_bytes(c, a["prefill_bytes_keys"],
                                 a["prefill_rows"]))
    least = max(ops / ctx.peak["flops_per_s"],
                moved / ctx.peak["bytes_per_s"])
    return 100.0 * least / secs
