"""Kernel ``kernels/paged_attention.py`` (deterministic fused kernel):
its device time against the FLOPs and bytes of the VALID context
(``counts.attention_flops`` / ``attention_bytes``) at the chip's peaks,
%."""

from benchmarks.chip import counts, readout

# The kernel runs as an unnamed Mosaic custom call; on an exact
# substrate it is the only one (the SC matmul kernel is named).
KERNEL, NOT = "tpu_custom_call", ("sc_fused_popcount",)


def read(ctx):
    red = ctx.reduction
    if red is None:
        return None
    c, b = ctx.cell.config, readout.act_bytes(ctx)
    rows = [(l, n) for t in ctx.window.ticks for l, n in t.rows if n]
    flops = sum(counts.attention_flops(c, l, n) for l, n in rows)
    byts = sum(counts.attention_bytes(c, l, n, b, b) for l, n in rows)
    return readout.roofline_share(ctx, red.kernel_ns(KERNEL, NOT), flops, byts)
