"""Trace reduction: busy union, per-kernel sums, step runs and idle gaps
labelled by the harness's host spans."""

import glob
import os

import pytest

from benchmarks.chip import trace_reduce

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "chip", "testdata")


def _plane(pid, name, lines, names):
    meta = "".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for i, n in enumerate(names, 1))
    body = ""
    for lid, (lname, events) in enumerate(lines, 1):
        evs = "".join(
            f"    events {{ metadata_id: {names.index(n) + 1} "
            f"offset_ps: {s * 1000} duration_ps: {(e - s) * 1000} }}\n"
            for n, s, e in events)
        body += (f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n'
                 f"{evs}  }}\n")
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


def synthetic():
    from jax.profiler import ProfileData
    dev_ops = [("fusion.1", 0, 10), ("custom-call.2", 5, 20),
               ("fusion.1", 30, 40), ("fusion.9", 55, 60)]   # last: outside
    mods = [("jit__unknown(1)", 0, 20), ("jit__sample_rows(2)", 30, 40)]
    host = [("measured window", 0, 50), ("plan", 20, 28),
            ("step dispatch", 28, 29), ("sample sync", 40, 50),
            ("unrelated", 20, 30)]
    names_d = sorted({n for n, _, _ in dev_ops + mods})
    names_h = sorted({n for n, _, _ in host})
    text = (_plane(1, "/device:TPU:0", [("XLA Ops", dev_ops),
                                        ("XLA Modules", mods)], names_d)
            + _plane(2, "/host:CPU", [("python3", host)], names_h))
    return ProfileData.from_text_proto(text)


def test_busy_is_the_union_of_device_operations_in_the_window():
    red = trace_reduce.reduce(synthetic())
    assert red.window == (0, 50)
    assert red.busy_ns == 30                      # [0, 20) and [30, 40)
    assert red.op_ns == {"fusion.1": 20, "custom-call.2": 15}
    assert red.kernel_ns("custom-call") == 15
    assert red.kernel_ns("fusion", exclude=(".9",)) == 20
    assert red.kernel_ns("absent") is None
    assert [m[0] for m in red.modules] == ["jit__unknown(1)",
                                           "jit__sample_rows(2)"]


def test_idle_gaps_carry_the_span_open_over_most_of_them():
    red = trace_reduce.reduce(synthetic())
    # [20, 30): plan covers 8 ns, step dispatch 1; [40, 50): sample sync
    assert sorted(red.gaps) == [("plan", 10), ("sample sync", 10)]


@pytest.mark.parametrize("ivs,lo,hi,busy,idle", [
    ([(0, 5), (3, 8), (10, 12)], 0, 15, [(0, 8), (10, 12)],
     [(8, 10), (12, 15)]),
    ([], 0, 4, [], [(0, 4)]),
    ([(1, 2)], 0, 2, [(1, 2)], [(0, 1)]),
])
def test_merge_and_idle(ivs, lo, hi, busy, idle):
    assert trace_reduce.merge(ivs) == busy
    assert trace_reduce.idle(busy, lo, hi) == idle


def test_a_recorded_chip_trace():
    """A short window of the harness on one v5e at small widths: the
    reduction agrees with sums taken directly over the raw events."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(TESTDATA, "*.xplane.pb"))[0]
    data = ProfileData.from_file(path)
    red = trace_reduce.reduce(data)
    lo, hi = red.window
    ops = []
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
    inside = [(max(s, lo), min(e, hi)) for s, e in ops if e > lo and s < hi]
    covered = sum(e - s for s, e in trace_reduce.merge(inside))
    assert red.busy_ns == pytest.approx(covered)
    assert 0 < red.busy_ns < red.window_ns
    assert sum(ns for _, ns in red.gaps) == pytest.approx(
        red.window_ns - red.busy_ns)
    labels = {name for name, _ in red.gaps}
    assert labels & set(trace_reduce.SPANS)
    assert red.kernel_ns("tpu_custom_call") > 0    # the attention kernel
    steps = [m for m in red.modules if "jit__unknown" in m[0]]
    assert steps and all(d > 0 for _, _, d in steps)


@pytest.mark.parametrize("text,want", [
    ('%closed_call.13 = f32[8,2,448,64]{3,2,1,0:T(8,128)S(1)} custom-call('
     's32[1280]{0:T(1024)S(1)} %g), custom_call_target="tpu_custom_call"',
     "%closed_call.13 custom-call tpu_custom_call"),
    ("%fusion.207 = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8,64] %a), "
     "kind=kLoop", "%fusion.207 fusion"),
    ("%while.5 = (s32[]{:T(128)}, bf16[8]) while((s32[], bf16[8]) %t)",
     "%while.5 while"),
])
def test_breakdown_names_ops_by_name_and_opcode(text, want):
    from benchmarks.chip import harness
    assert harness.short_op(text) == want
