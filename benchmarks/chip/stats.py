"""End-to-end metric arithmetic over one measured window.

It reads the harness's host records: the positions each tick fed and
when the tick started.  The number is taken over all the work and all
the time of the window; nothing is a median of chunks.
"""

from __future__ import annotations


def tokens_per_s(ticks, start: float, end: float) -> float:
    """Positions fed for requests (prompt tokens and fed-back generated
    tokens; chunk padding and idle rows excluded) by every tick that
    started in the window, over the window.  The window ends with the
    first tick that finished at or after the nominal end, so ticks are
    counted whole."""
    fed = sum(t.positions for t in ticks if start <= t.start < end)
    return fed / (end - start)
