"""Kernel ``kernels/sc_fused.py``: its device time against the matmul
FLOPs and operand bytes of every SC dense site (``counts.sc_site_work``:
the same work whatever implements it), at the bf16 peak, %."""

from benchmarks.chip import counts, readout

KERNEL = "sc_fused_popcount"     # the Mosaic call of kernels/sc_fused.py


def read(ctx):
    red = ctx.reduction
    if red is None:
        return None
    c = ctx.cell.config
    work = [counts.sc_site_work(c, t.rows) for t in ctx.window.ticks]
    flops = sum(f for f, _ in work)
    byts = sum(b for _, b in work)
    return readout.roofline_share(ctx, red.kernel_ns(KERNEL), flops, byts)
