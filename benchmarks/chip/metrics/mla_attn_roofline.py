"""Kernel ``mla_paged_attention`` (``kernels/paged_attention.py``, the
absorbed latent attention): its device time against the FLOPs and bytes
of the VALID context (``counts_mla_moe.attention_flops`` /
``attention_bytes``) at the chip's peaks, %."""

from benchmarks.chip import counts_mla_moe as counts, readout

KERNEL = "mla_paged_attention"


def read(ctx):
    red = ctx.reduction
    if red is None:
        return None
    c, b = ctx.cell.config, readout.act_bytes(ctx)
    rows = [(l, n) for t in ctx.window.ticks for l, n in t.rows if n]
    flops = sum(counts.attention_flops(c, l, n) for l, n in rows)
    byts = sum(counts.attention_bytes(c, l, n, b, b) for l, n in rows)
    return readout.roofline_share(ctx, counts.kernel_ns(red, KERNEL), flops,
                                  byts)
