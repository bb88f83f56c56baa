"""Whole step of the latent-attention MoE model: useful FLOPs of the
window in the absorbed form (``counts_mla_moe.step_flops``: fed
positions through every layer, top-k routed and shared experts, valid
attention context, the output head of rows that produced logits) over
the window at the chip's bf16 peak, %."""

from benchmarks.chip import counts_mla_moe as counts


def read(ctx):
    red = ctx.reduction
    if red is None or not red.window_ns:
        return None
    c = ctx.cell.config
    flops = sum(counts.step_flops(c, t.rows) for t in ctx.window.ticks)
    return 100.0 * flops / (red.window_ns / 1e9 * ctx.peaks["bf16_flops"])
