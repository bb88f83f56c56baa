"""Scheduler: mean host ms of ``Scheduler.plan`` per tick in the window
(the harness wraps the call)."""


def read(ctx):
    ticks = ctx.window.ticks
    return sum(t.plan_s for t in ticks) / len(ticks) * 1e3 if ticks else None
