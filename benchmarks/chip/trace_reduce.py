"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData`` alone.  Device planes are those
named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event
per operation the device ran and the ``XLA Modules`` line one event per
executable run.  The harness's host spans (``jax.profiler.
TraceAnnotation``) sit on the host plane, on the same clock.

What comes out, all inside the measured window (the host span named
``WINDOW``):

* ``busy_ns`` — the union of the device's operation intervals, averaged
  over the chips in the trace;
* ``op_ns`` — device time per operation name, and ``op_text`` the
  operation's names and string stats, for matching kernels;
* ``modules`` — (name, start, duration) of every executable run, in
  order: per-executable step time;
* ``gaps`` — every idle interval of chip 0, labelled with the harness
  span open over most of it (``OTHER`` where none is).
"""

from __future__ import annotations

import collections
import dataclasses

WINDOW = "measured window"
OTHER = "other host"
SPANS = ("plan", "step dispatch", "sample sync")


@dataclasses.dataclass
class Reduction:
    window: tuple                   # (start_ns, end_ns)
    chips: int
    busy_ns: float                  # mean over chips
    op_ns: dict                     # op name -> device ns (all chips)
    op_text: dict                   # op name -> names and string stats
    modules: list                   # [(name, start_ns, dur_ns)], chip 0
    gaps: list                      # [(label, ns)], chip 0, longest first

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def kernel_ns(self, needle: str, exclude: tuple = ()) -> float | None:
        """Device ns of the operations whose name or stats contain
        ``needle`` and none of ``exclude``; None when none matches."""
        hit = [n for n, text in self.op_text.items()
               if needle in text and not any(x in text for x in exclude)]
        return sum(self.op_ns[n] for n in hit) if hit else None


def merge(intervals) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def idle(busy, lo, hi) -> list:
    """Complement of disjoint sorted ``busy`` inside [lo, hi]."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def label(gap, spans) -> str:
    """The span overlapping ``gap`` the most, else ``OTHER``."""
    best, name = 0.0, OTHER
    for sname, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, sname
    return name


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, e)
            for e in line.events]


def reduce(data) -> Reduction:
    """``data``: a ``ProfileData`` or a path to an ``.xplane.pb``."""
    if isinstance(data, str):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(data)
    devices, spans, window = [], [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for name, s, e, _ in _events(ln):
                    if name == WINDOW:
                        window = (s, e)
                    elif name in SPANS:
                        spans.append((name, s, e))
    if not devices:
        raise ValueError("the trace has no /device:TPU plane")
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} host span")
    lo, hi = window
    op_ns: dict = collections.defaultdict(float)
    op_text: dict = {}
    busy_total, modules, gaps = 0.0, [], []
    for i, lines in enumerate(devices):
        ops = []
        if "XLA Ops" in lines:
            for name, s, e, ev in _events(lines["XLA Ops"]):
                iv = clip([(s, e)], lo, hi)
                if not iv:
                    continue
                ops.append(iv[0])
                op_ns[name] += iv[0][1] - iv[0][0]
                if name not in op_text:
                    op_text[name] = " ".join(
                        [name] + [str(v) for _, v in ev.stats
                                  if isinstance(v, str)])
        busy = merge(ops)
        busy_total += sum(e - s for s, e in busy)
        if i == 0:
            if "XLA Modules" in lines:
                modules = [(name, s, e - s)
                           for name, s, e, _ in _events(lines["XLA Modules"])
                           if lo <= s < hi]
                modules.sort(key=lambda m: m[1])
            gaps = sorted(((label(g, spans), g[1] - g[0])
                           for g in idle(busy, lo, hi)),
                          key=lambda g: -g[1])
    return Reduction(window=window, chips=len(devices),
                     busy_ns=busy_total / len(devices), op_ns=dict(op_ns),
                     op_text=op_text, modules=modules, gaps=gaps)
