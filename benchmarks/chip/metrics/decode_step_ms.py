"""Model step: mean device ms of a width-1 ``decode_paged`` run (from
the trace)."""

from benchmarks.chip import readout


def read(ctx):
    return readout.step_ms(ctx, width_one=True)
