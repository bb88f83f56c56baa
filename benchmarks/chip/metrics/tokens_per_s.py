"""Positions fed for requests per second over the window, whole ticks
(``stats.tokens_per_s``)."""

from benchmarks.chip import stats


def read(ctx):
    w = ctx.window
    return stats.tokens_per_s(w.ticks, w.start, w.end)
