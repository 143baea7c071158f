"""step.mfu: the whole step's share of the chips' bf16 peak (%).

Model FLOPs of the tokens whose gradients entered an update in the
traced window (forward and backward, recomputation not counted;
``flops.train_flops_per_token``), over chips x peak x the window's
seconds. A kernel taken off the path leaves its roofline silent; this
share still bounds what the whole step achieves.
"""


def read(ctx):
    window = ctx.trace_lib.window_s(ctx.trace)
    if ctx.useful_tokens <= 0 or window <= 0:
        return None
    work = ctx.useful_tokens * ctx.flops.train_flops_per_token(
        ctx.sizes, ctx.traffic.seq_len)
    return 100.0 * work / (ctx.chips * ctx.peaks["bf16_flops_per_s"] * window)
