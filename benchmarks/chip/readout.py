"""Shared arithmetic of the per-layer readers (``metrics/*.py``)."""

from __future__ import annotations

# The engine's jitted step (``jax.jit(partial(lm.decode_paged, ...))``)
# runs as an executable the trace names ``jit__unknown(<id>)``: a partial
# has no name.  It is the only unnamed executable on the serving path.
STEP_MODULES = ("decode_paged", "jit__unknown")


def step_ms(ctx, width_one: bool):
    """Mean device ms of the step executable over the window's ticks of
    one kind (width 1, or chunk width).  The step's runs in the trace
    are paired with the harness's ticks in order; None where they do
    not pair one to one, or where no tick of the kind ran."""
    red = ctx.reduction
    if red is None:
        return None
    runs = [m for m in red.modules
            if any(name in m[0] for name in STEP_MODULES)]
    ticks = ctx.window.ticks
    if len(runs) != len(ticks):
        return None
    durs = [d for (_, _, d), t in zip(runs, ticks)
            if (t.width == 1) == width_one]
    return sum(durs) / len(durs) / 1e6 if durs else None


def act_bytes(ctx) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[
        ctx.cell.config["program"]["dtype"]]


def roofline_share(ctx, kernel_ns, flops: float, byts: float):
    """Least time at the chip's peaks (compute at the bf16 peak, or
    bytes at the HBM peak) over the kernel's device time, in %."""
    if not kernel_ns or not flops:
        return None
    least = max(flops / ctx.peaks["bf16_flops"],
                byts / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (kernel_ns / 1e9)
