"""The harness's window loop, rehearsed on the CPU: against a stand-in
engine (exact timing rules) and, end to end, through the real engine at
small widths with the device check stubbed."""

import dataclasses
import json
import os
import time

import pytest

from benchmarks.chip import harness, traffic

HERE = os.path.dirname(os.path.abspath(__file__))


# -- the loop's rules, against a stand-in engine ----------------------------


class _Sched:
    def __init__(self):
        self.waiting, self.rows = [], []

    def has_work(self):
        return bool(self.waiting or self.rows)


class FakeEngine:
    """``slots`` rows; each tick sleeps ``tick_s`` and gives every row
    one token."""

    def __init__(self, ticks, slots=2, tick_s=0.01):
        self.scheduler, self.slots, self.tick_s = _Sched(), slots, tick_s
        self.ticks = ticks

    def submit(self, req):
        self.scheduler.waiting.append(req)

    def step(self):
        s = self.scheduler
        while s.waiting and len(s.rows) < self.slots:
            s.rows.append(s.waiting.pop(0))
        start = time.perf_counter()
        time.sleep(self.tick_s)
        self.ticks.append(harness.Tick(start=start, plan_s=0.0, width=1,
                                       rows=[(0, 1)] * len(s.rows)))
        for r in list(s.rows):
            r.generated.append(5)
            if len(r.generated) >= r.max_new_tokens:
                r.done = True
                s.rows.remove(r)
        return True


class _Clock:
    events = 0


def _items(n, max_new=3):
    return [traffic.Item(prompt=[3, 4], max_new=max_new) for i in range(n)]


def test_window_ends_with_the_first_tick_past_its_length():
    ticks = []
    eng = FakeEngine(ticks, slots=1, tick_s=0.07)
    w = harness.run_window(eng, _items(50, max_new=100), 1, 0.2, ticks,
                           _Clock())
    assert w.end >= w.start + 0.2
    assert w.end == pytest.approx(w.ticks[-1].end)
    assert w.ticks[-1].start < w.start + 0.2    # the tick counted whole
    assert w.compiles == 0


def test_closed_loop_keeps_its_clients_in_flight():
    ticks = []
    eng = FakeEngine(ticks, slots=8, tick_s=0.005)
    w = harness.run_window(eng, _items(40, max_new=2), 3, 0.1, ticks,
                           _Clock())
    assert all(len(t.rows) == 3 for t in w.ticks)
    assert w.attempted == len(w.reqs) > 3
    assert [r.rid for r in w.reqs] == list(range(w.attempted))


def test_a_pool_that_runs_dry_inside_the_window_fails():
    ticks = []
    eng = FakeEngine(ticks, slots=2, tick_s=0.005)
    with pytest.raises(RuntimeError, match="ran dry"):
        harness.run_window(eng, _items(4, max_new=1), 2, 0.2, ticks,
                           _Clock())


# -- end to end at small widths, device check stubbed -----------------------


def tiny_cell(name="qwen2-0.5b.chat", config="tiny_chat.json"):
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    with open(os.path.join(HERE, config)) as f:
        cfg = json.load(f)
    mix = {"clients": 4, "pool": 256,
           "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                      "min": 4, "max": 24},
           "output": {"dist": "uniform", "min": 2, "max": 8},
           "base_seed": 1}
    if cfg["check"] == "sc_cosine":
        mix = {"clients": 1, "pool": 64,
               "prompt": {"dist": "choice", "values": [4, 8]},
               "output": {"dist": "choice", "values": [3]}, "base_seed": 2}
    return harness.Cell(
        name=name, chips=1, config=cfg, mix=mix,
        end_to_end=[m for m in bench["end_to_end"]
                    if harness._applies(m, name)],
        per_layer=[m for m in bench["per_layer"]
                   if harness._applies(m, name)])


def drive(monkeypatch, capsys, cell, argv):
    """``run.py``'s main with the device check stubbed and the cell
    given; returns (last stdout line, stderr lines)."""
    import importlib
    run = importlib.import_module("benchmarks.chip.run")
    monkeypatch.setattr(harness, "require_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    assert run.main(argv) == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err.strip().splitlines()


REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


def test_run_prints_one_result_line_with_its_checks_last(monkeypatch,
                                                          capsys):
    cell = tiny_cell()
    line, err = drive(monkeypatch, capsys, cell, [
        "--workload", cell.name, "--seed", str(2**33 + 1), "--seconds",
        "1.5", "--trace", "0"])
    assert list(line) == REQUIRED + ["checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > cell.mix["clients"]
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == 1
    # the checks are the last lines of standard error, each with its limit
    assert err[-1].startswith("check max_gap ") and "limit" in err[-1]
    assert " 0 compile events in the window" in "\n".join(err)


def test_run_fails_without_a_tpu(capsys):
    import importlib
    run = importlib.import_module("benchmarks.chip.run")
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "qwen2-0.5b.chat", "--seed", "1",
                  "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_a_device_kind_missing_from_the_peaks_table_fails(monkeypatch):
    import jax

    @dataclasses.dataclass
    class Dev:
        platform: str = "tpu"
        device_kind: str = "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(SystemExit, match="peaks.json"):
        harness.require_device(1)
    monkeypatch.setattr(jax, "devices", lambda: [Dev(device_kind="TPU v5 lite")])
    with pytest.raises(SystemExit, match="4 chips"):
        harness.require_device(4)
    assert harness.require_device(1) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
