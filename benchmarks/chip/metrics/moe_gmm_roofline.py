"""Kernel ``moe_gmm`` (``kernels/moe_gmm.py``, the dropless grouped
expert matmul): its device time against the FLOPs and bytes of the fed
positions' (token, expert) pairs (``counts_mla_moe.gmm_work``) at the
chip's peaks, %."""

from benchmarks.chip import counts_mla_moe as counts, readout

KERNEL = "moe_gmm"


def read(ctx):
    red = ctx.reduction
    if red is None:
        return None
    c, b = ctx.cell.config, readout.act_bytes(ctx)
    work = [counts.gmm_work(c, t.positions, b, b) for t in ctx.window.ticks]
    flops = sum(f for f, _ in work)
    byts = sum(w for _, w in work)
    return readout.roofline_share(ctx, counts.kernel_ns(red, KERNEL), flops,
                                  byts)
