"""Model step: mean device ms of a chunk-width ``decode_paged`` run
(from the trace)."""

from benchmarks.chip import readout


def read(ctx):
    return readout.step_ms(ctx, width_one=False)
