"""Run one cell once: set-up, the measured window, the check, the line.

Everything a cell needs is found by name: its entry in ``BENCHMARK.json``
names a configuration (``configs/<file>``), a traffic mix
(``traffic/<mix>.json``) and its metrics (``metrics/<name>.py``).
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import os
import re
import shutil
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
WARM_RID = 2**30          # warm-up requests never collide with traffic


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# what a cell is
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list        # metric entries of BENCHMARK.json
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(wl)}")
    w = wl[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=w["chips"],
        config=load_json(os.path.join(ROOT, cfg["file"])),
        mix=load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def reader(metric: str):
    """The ``read(ctx)`` of ``metrics/<metric>.py``."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no reader for metric {metric!r} under metrics/")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.chip.metrics." + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def require_device(chips: int) -> dict:
    """The accelerator the cell runs on; exits when there is none."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"the chip benchmark needs a TPU; JAX found "
                         f"{platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    peaks_for(devices[0].device_kind)
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and how many
    such events there were, from JAX's own monitoring events (as
    ``chip_smoke.CompileClock``)."""

    def __init__(self):
        import jax
        self.secs, self.events = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.secs += duration
            self.events += 1


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


_SIZE_KEYS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads",
              "intermediate_size": "d_ff", "num_hidden_layers": "n_layers",
              "vocab_size": "vocab", "rope_theta": "rope_theta",
              "tie_word_embeddings": "tie_embeddings"}


def model_config(config: dict):
    """The program's ModelConfig of ``program.arch`` with every size the
    configuration file states put in: the file is what runs."""
    import jax.numpy as jnp
    from repro.configs import get_config
    prog = config["program"]
    dtype = jnp.dtype(prog["dtype"])
    return get_config(prog["arch"]).replace(
        param_dtype=dtype, act_dtype=dtype, sc_backend=prog["sc_backend"],
        sc_nbit=prog["sc_nbit"], paged_attn=prog["paged_attn"], head_dim=0,
        **{attr: config[key] for key, attr in _SIZE_KEYS.items()})


@dataclasses.dataclass
class Tick:
    start: float
    plan_s: float
    width: int
    rows: list              # [(ctx, n_valid)] per slot
    end: float = 0.0

    @property
    def positions(self) -> int:
        return sum(n for _, n in self.rows)


class Instrument:
    """Host spans and tick records around the engine's calls.

    Wraps ``Scheduler.plan`` (span ``plan``, host time, the rows fed),
    the jitted step (span ``step dispatch``; a width-1 step is waited for
    there, as the engine waits for it anyway) and the sampler (span
    ``sample sync``: it returns once the tokens are on the host)."""

    def __init__(self, engine):
        import jax
        from jax.profiler import TraceAnnotation
        self.ticks: list = []
        sched, step, sample = (engine.scheduler, engine._step_fn,
                               engine._sample_fn)
        plan = sched.plan

        def timed_plan():
            t = time.perf_counter()
            with TraceAnnotation("plan"):
                p = plan()
            if p is not None:
                self.ticks.append(Tick(
                    start=t, plan_s=time.perf_counter() - t, width=p.sc,
                    rows=list(zip(p.lengths, p.n_valid))))
            return p

        def spanned_step(*a, **k):
            with TraceAnnotation("step dispatch"):
                out = step(*a, **k)
                if a[3].shape[1] == 1:
                    jax.block_until_ready(out[0])
            return out

        def synced_sample(*a):
            with TraceAnnotation("sample sync"):
                return jax.block_until_ready(sample(*a))

        sched.plan = timed_plan
        engine._step_fn = spanned_step
        engine._sample_fn = synced_sample


def record_logits(engine) -> dict:
    """rid -> the logits each of its tokens was sampled from (as
    ``chip_smoke.record_logits``): the engine's own output."""
    import numpy as np
    out: dict = {}
    latest: dict = {}
    sample_fn, on_token = engine._sample_fn, engine.scheduler.on_token

    def sample(keys, logits, temps):
        latest["logits"] = logits
        return sample_fn(keys, logits, temps)

    def token(slot, seq, tok):
        out.setdefault(seq.req.rid, []).append(
            np.asarray(latest["logits"][slot]))
        on_token(slot, seq, tok)

    engine._sample_fn = sample
    engine.scheduler.on_token = token
    return out


def build(cell: Cell, seed: int):
    """(cfg, params, engine, instrument, logits record or None)."""
    from repro import serve
    from benchmarks.chip import weights
    cfg = model_config(cell.config)
    params = weights.make_params(cfg, seed)
    opts = serve.ServeOptions(paged=True, seed=seed % 2**31, eos_id=-1,
                              **cell.config["serve"])
    engine = serve.build_engine(params, cfg, opts)
    inst = Instrument(engine)
    logits = (record_logits(engine)
              if cell.config["check"] == "sc_cosine" else None)
    return cfg, params, engine, inst, logits


def warm_up(engine, cell: Cell) -> None:
    """Run the shapes the traffic uses, and only those: one chunk-width
    prefill tick, width-1 ticks and the sampler, through the engine."""
    import jax
    from repro.serve import Request
    chunk = cell.config["serve"]["prefill_chunk"]
    engine.submit(Request(rid=WARM_RID, prompt=[3] * (chunk + 1),
                          max_new_tokens=2, temperature=0.0))
    engine.run_until_drained()
    jax.block_until_ready(engine.pages)
    engine.scheduler.finished.clear()


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    start: float
    end: float
    reqs: list              # every request submitted in the window
    ticks: list
    compiles: int

    @property
    def attempted(self) -> int:
        return len(self.reqs)


def run_window(engine, items, clients: int, seconds: float,
               instrument_ticks, clock) -> Window:
    """Drive the engine for ``seconds`` with ``clients`` requests in
    flight, each replaced the moment it finishes, one tick at a time.
    The window ends with the first tick that finishes at or after
    ``seconds``, so every tick in it counts whole."""
    from repro.serve import Request
    reqs, inflight = [], []
    compiles0 = clock.events
    first_tick = len(instrument_ticks)

    def submit():
        if len(reqs) == len(items):
            raise RuntimeError(f"the mix's pool of {len(items)} requests "
                               f"ran dry inside the window")
        it = items[len(reqs)]
        req = Request(rid=len(reqs), prompt=it.prompt,
                      max_new_tokens=it.max_new, temperature=0.0)
        engine.submit(req)
        reqs.append(req)
        inflight.append(req)

    start = time.perf_counter()
    end_at = start + seconds
    for _ in range(clients):
        submit()
    while True:
        engine.step()
        end = time.perf_counter()
        instrument_ticks[-1].end = end
        for req in [r for r in inflight if r.done]:
            inflight.remove(req)
            submit()
        if end >= end_at:
            break
    return Window(start=start, end=end, reqs=reqs,
                  ticks=instrument_ticks[first_tick:],
                  compiles=clock.events - compiles0)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Context:
    """What a metric reader sees."""
    cell: Cell
    window: Window
    setup_s: float
    peaks: dict
    reduction: object = None        # trace_reduce.Reduction, traced runs


def _metrics(entries, ctx) -> dict:
    out = {}
    for m in entries:
        v = reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def _checks(cell: Cell, result: dict) -> dict:
    lim = cell.config["limits"]
    if cell.config["check"] == "greedy_gap":
        return {"max_gap": {"value": result["max_gap"],
                            "limit": lim["max_gap"]}}
    out = {"cos_mean_floor": {"value": result["cos_mean"],
                              "limit": lim["cos_mean_floor"]},
           "cos_mean_ceiling": {"value": result["cos_mean"],
                                "limit": lim["cos_mean_ceiling"]}}
    if "argmax_mismatch" in result:
        out["argmax_mismatch"] = {"value": result["argmax_mismatch"],
                                  "limit": 0}
    return out


def _passes(name: str, c: dict) -> bool:
    if name == "cos_mean_floor":
        return c["value"] >= c["limit"]
    return c["value"] <= c["limit"]


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        device: dict, control: bool = False, log=print) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax
    from benchmarks.chip import check, traffic, trace_reduce, weights

    clock = CompileClock()
    t_build = time.perf_counter()
    cfg, params, engine, inst, logits = build(cell, seed)
    jax.block_until_ready(params)
    t_warm = time.perf_counter()
    warm_up(engine, cell)
    t_ready = time.perf_counter()
    setup_compiles = (clock.events, clock.secs)
    items = traffic.generate(cell.mix, seed, cfg.vocab)
    if logits is not None:
        logits.clear()
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    setup_s = time.perf_counter() - t_start
    if trace:
        # the Python tracer would record every host call: it slows the
        # host loop it measures and swells the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        win = run_window(engine, items, cell.mix["clients"], seconds,
                         inst.ticks, clock)
        jax.block_until_ready(engine.pages)
    if trace:
        jax.profiler.stop_trace()
    used = jax.devices()[:cell.chips]
    device = dict(device, memory_peak_bytes=max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used))
    ctx = Context(cell=cell, window=win, setup_s=setup_s,
                  peaks=peaks_for(device["kind"]))
    if trace:
        path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        ctx.reduction = trace_reduce.reduce(path)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = ctx.reduction.busy_ns / 1e9
        device["window_s"] = ctx.reduction.window_ns / 1e9
    metrics = _metrics(cell.per_layer if trace else cell.end_to_end, ctx)

    # the check: engine freed, weights drawn again from the seed
    done = [r for r in win.reqs if r.done]
    served = [r for r in win.reqs if r.generated]
    engine_logits = logits
    # the instrument's wrappers reach the engine (scheduler -> on_finish)
    del engine, params, inst
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    quant = cell.config["control"] if control else None
    params = weights.make_params(cfg, seed)
    serve_cfg = cell.config["serve"]
    pad_len = serve_cfg["max_len"]
    max_new = max(it.max_new for it in items)
    if cell.config["check"] == "greedy_gap":
        reqs = check.sample(served, seed, cell.config["check_tokens"])
        result = (check.greedy_gap(params, cell.config, reqs, pad_len,
                                   max_new, quant) if reqs else None)
    else:
        reqs = served
        result = (check.sc_cosine(params, cell.config, reqs, engine_logits,
                                  pad_len, max_new, quant) if reqs else None)
    del params
    if result is None:
        checks, failed = {"checked_requests": {"value": 0, "limit": 1}}, 0
        correct = False
    else:
        checks = _checks(cell, result)
        correct = all(_passes(k, v) for k, v in checks.items())
        failed = (sum(g > cell.config["limits"]["max_gap"]
                      for g in result["per_request"])
                  if "per_request" in result else int(not correct))
    log(f"set-up {setup_s:.3f} s: {t_build - t_start:.3f} to start, "
        f"{t_warm - t_build:.3f} weights and engine, {t_ready - t_warm:.3f} "
        f"warm-up; {setup_compiles[0]} compile events "
        f"({setup_compiles[1]:.3f} s) in it")
    log(f"window {win.end - win.start:.3f} s, {len(win.ticks)} ticks, "
        f"{win.attempted} requests attempted, {len(done)} finished, "
        f"{win.compiles} compile events in the window, {len(reqs)} "
        f"requests checked, {live} device bytes live when the reference "
        f"started")
    if control:
        log(f"control: the reference in {quant} in the program's place")
    line = {"correct": bool(correct), "attempted": win.attempted,
            "failed": int(failed), "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = breakdown(ctx.reduction)
    line["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    return line


_CONTAINERS = (" while(", " conditional(", " call(")
_OPCODE = re.compile(r"=\s.*?\s([a-z][\w-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_op(text: str) -> str:
    """``%name opcode [custom-call target]`` of a trace's HLO op line."""
    parts = [text.split(" = ")[0]]
    for pattern in (_OPCODE, _TARGET):
        m = pattern.search(text)
        if m:
            parts.append(m.group(1))
    return " ".join(parts)


def breakdown(red) -> dict:
    """Top device operations (loop and call containers left out: their
    time is their bodies') and the longest idle gaps by host span."""
    ops = sorted(((n, ns) for n, ns in red.op_ns.items()
                  if not any(c in n for c in _CONTAINERS)),
                 key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[short_op(n), ns / 1e9] for n, ns in ops],
            "idle_gaps": [[n, ns / 1e9] for n, ns in red.gaps[:10]]}
