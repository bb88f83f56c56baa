"""Unified serve/substrate telemetry (``repro.obs``).

Three layers under test: the registry/tracer primitives themselves, the
substrate hooks (sc dispatch counters, autotune hit/miss, arch pricing
folded into spans — all default-off), and the serving integration — a
drained paged run must emit a parseable metrics snapshot, a Prometheus
exposition, and a trace JSONL whose span counts MATCH the engine's
lifecycle events exactly.
"""

import gc
import glob
import json
import math
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.ft import supervisor
from repro.models import lm, params as P
from repro.sc import autotune
from repro.sc.config import ScConfig
from repro.sc.registry import sc_dot, sc_dot_rows
from repro.serve import PagedServeConfig, PagedServingEngine, Request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import obs_report  # noqa: E402

F32 = dict(param_dtype=jnp.float32, act_dtype=jnp.float32)


def _cfg(**kw):
    return get_smoke_config("qwen2-0.5b").replace(**F32, **kw)


# ---------------------------------------------------------------------------
# Metrics registry primitives
# ---------------------------------------------------------------------------


def test_counter_inc_and_labels():
    reg = obs.MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc()
    c.inc(2, kind="a")
    c.inc(kind="a")
    assert c.value() == 1
    assert c.value(kind="a") == 3
    assert c.value(kind="missing") == 0
    assert reg.value("req_total", kind="a") == 3
    assert reg.value("nope") is None


def test_counter_rejects_negative():
    c = obs.MetricsRegistry().counter("x_total")
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)


def test_disabled_registry_records_nothing():
    reg = obs.MetricsRegistry(enabled=False)
    c = reg.counter("x_total")
    g = reg.gauge("depth")
    h = reg.histogram("lat_ms")
    c.inc(5)
    g.set(3)
    h.observe(1.0)
    assert c.value() == 0 and g.value() is None and h.count() == 0
    reg.enable()
    c.inc(5)
    assert c.value() == 5


def test_gauge_set_add():
    g = obs.MetricsRegistry().gauge("depth")
    g.set(4)
    g.add(-1)
    assert g.value() == 3


def test_registry_idempotent_and_kind_mismatch_raises():
    reg = obs.MetricsRegistry()
    a = reg.counter("x_total", "help")
    assert reg.counter("x_total") is a
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("x_total")


def test_histogram_percentiles_bounded_by_buckets():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0, 8.0))
    assert h.percentile(50) is None
    for v in (0.5, 1.5, 1.5, 3.0, 20.0):
        h.observe(v)
    assert h.count() == 5
    assert h.sum() == pytest.approx(26.5)
    p50 = h.percentile(50)
    assert 1.0 <= p50 <= 2.0          # covering-bucket bound
    # overflow bucket clamps to the observed max, never +inf
    assert h.percentile(99) <= 20.0
    assert h.percentile(0) >= 0.5


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError, match="ascending"):
        obs.MetricsRegistry().histogram("h", buckets=(2.0, 1.0))


def test_snapshot_shape_and_exposition_parse():
    reg = obs.MetricsRegistry()
    reg.counter("req_total", "requests").inc(3, kind="a")
    reg.gauge("depth", "queue").set(2)
    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    snap = reg.snapshot()
    assert snap["counters"] == {"req_total{kind=a}": 3}
    assert snap["gauges"] == {"depth": 2}
    hs = snap["histograms"]["lat_ms"]
    assert hs["count"] == 2 and hs["sum"] == pytest.approx(5.5)
    assert hs["min"] == 0.5 and hs["max"] == 5.0
    assert json.loads(reg.snapshot_json()) == snap
    # the exposition round-trips through the report tool's parser
    parsed = obs_report.parse_exposition(reg.exposition())
    assert parsed["counters"] == {"req_total{kind=a}": 3}
    assert parsed["gauges"] == {"depth": 2}
    assert parsed["histograms"]["lat_ms"] == {"count": 2, "sum": 5.5}
    text = reg.exposition()
    assert "# TYPE req_total counter" in text
    assert 'lat_ms_bucket{le="+Inf"} 2' in text


def test_registry_thread_safety_smoke():
    reg = obs.MetricsRegistry()
    c = reg.counter("n_total")

    def worker():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 4000


# ---------------------------------------------------------------------------
# Tracer primitives
# ---------------------------------------------------------------------------


def test_tracer_span_nesting_and_attr():
    tr = obs.Tracer()
    with tr.span("outer", a=1):
        tr.event("ev", b=2)
        with tr.span("inner"):
            tr.attr(c=3)              # folds into the INNERMOST open span
    assert tr.counts() == {"outer": 1, "ev": 1, "inner": 1}
    by_name = {s.name: s for s in tr.spans}
    assert by_name["outer"].parent_id is None
    assert by_name["ev"].parent_id == by_name["outer"].span_id
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["inner"].attrs == {"c": 3}
    assert by_name["outer"].dur_ns >= by_name["inner"].dur_ns >= 0
    assert by_name["ev"].dur_ns == 0


def test_null_tracer_is_inert():
    with obs.NULL_TRACER.span("x"):
        obs.NULL_TRACER.event("y")
        obs.NULL_TRACER.attr(z=1)
    assert obs.NULL_TRACER.spans == []


def test_tracer_jsonl_roundtrip_and_chrome(tmp_path):
    tr = obs.Tracer()
    with tr.span("tick", n=1):
        tr.event("sub")
    path = tr.write_jsonl(str(tmp_path / "t.jsonl"))
    rows = obs.read_jsonl(path)
    assert [r["name"] for r in rows] == ["sub", "tick"]
    chrome = obs.to_chrome(rows)
    events = chrome["traceEvents"]
    assert events[0]["ph"] == "M"               # process_name metadata
    phs = {e["name"]: e["ph"] for e in events[1:]}
    assert phs == {"sub": "i", "tick": "X"}
    tick = next(e for e in events if e["name"] == "tick")
    assert tick["dur"] > 0 and tick["args"]["n"] == 1


def test_install_tracer_slot():
    assert obs.current_tracer() is None
    tr = obs.install_tracer(obs.Tracer())
    try:
        assert obs.current_tracer() is tr
        # conditional uninstall of a DIFFERENT tracer leaves it in place
        obs.uninstall_tracer(obs.Tracer())
        assert obs.current_tracer() is tr
    finally:
        obs.uninstall_tracer(tr)
    assert obs.current_tracer() is None


def test_tracer_span_reaches_the_profiler_trace(tmp_path):
    """An enabled tracer's span comes back from a CPU profiler trace
    under its name, with the attributes given at open and annotated
    before it closed as stats; ``attr`` stays in memory only."""
    from jax.profiler import ProfileData
    tr = obs.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("engine.tick", kind="prefill", live=8) as s:
            s.annotate(fed=3)
            tr.attr(note="memory only")
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    events = [e for p in ProfileData.from_file(path).planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events if e.name == "engine.tick"]
    assert len(events) == 1 and events[0].duration_ns > 0
    assert list(events[0].stats) == [("kind", "prefill"), ("live", 8),
                                     ("fed", 3)]
    assert tr.spans[0].attrs == {"kind": "prefill", "live": 8, "fed": 3,
                                 "note": "memory only"}
    assert tr.spans[0].annotation is None          # closed with the span


def test_installed_tracer_spans_garbage_collections():
    """An installed enabled tracer records a python.gc span per
    collection; uninstalling removes the hook, and a disabled tracer
    never adds one."""
    tr = obs.install_tracer(obs.Tracer())
    try:
        gc.collect()
    finally:
        obs.uninstall_tracer(tr)
    spans = [s for s in tr.spans if s.name == "python.gc"]
    assert spans and spans[-1].attrs["generation"] == 2
    assert spans[-1].attrs["collected"] >= 0
    n = len(tr.spans)
    gc.collect()
    assert len(tr.spans) == n
    off = obs.install_tracer(obs.Tracer(enabled=False))
    try:
        assert off._on_gc not in gc.callbacks
    finally:
        obs.uninstall_tracer(off)
    assert tr._on_gc not in gc.callbacks


# ---------------------------------------------------------------------------
# Substrate hooks: sc dispatch, autotune, arch pricing (default-off)
# ---------------------------------------------------------------------------


@pytest.fixture
def global_obs():
    """Enable the default registry + install a tracer, restore after."""
    reg = obs.enable()
    reg.clear()
    tr = obs.install_tracer(obs.Tracer())
    try:
        yield reg, tr
    finally:
        obs.uninstall_tracer(tr)
        obs.disable()
        reg.clear()


def test_sc_dispatch_counters_and_span(global_obs):
    reg, tr = global_obs
    key = jax.random.PRNGKey(0)
    x = jax.random.uniform(key, (4, 8))
    w = jax.random.uniform(key, (8, 4))
    cfg = ScConfig(backend="array", nbit=64)
    sc_dot(key, x, w, cfg)
    keys = jnp.broadcast_to(jax.random.key_data(key)[None], (4, 2))
    sc_dot_rows(keys, x, w, cfg)
    snap = reg.snapshot()["counters"]
    assert snap["sc_dispatch_total{backend=array,entry=sc_dot}"] == 1
    assert snap["sc_dispatch_total{backend=array,entry=sc_dot_rows}"] == 1
    # arch pricing only records under an installed arch-trace collector
    assert "arch_sc_dot_calls_total" not in snap
    spans = [s for s in tr.spans if s.name == "sc.dispatch"]
    assert len(spans) == 2
    assert spans[0].attrs["backend"] == "array"
    assert spans[0].attrs["m"] == 4 and spans[0].attrs["k"] == 8


def test_sc_dispatch_silent_when_disabled():
    reg = obs.default_registry()
    assert not reg.enabled      # the process-global default-off contract
    before = dict(reg.snapshot()["counters"])
    key = jax.random.PRNGKey(0)
    sc_dot(key, jax.random.uniform(key, (2, 4)),
           jax.random.uniform(key, (4, 2)), ScConfig(backend="array",
                                                     nbit=64))
    assert reg.snapshot()["counters"] == before


def test_arch_pricing_folds_into_dispatch_span(global_obs):
    from repro.arch import trace as arch_trace
    reg, tr = global_obs
    key = jax.random.PRNGKey(0)
    with arch_trace.collect():
        sc_dot(key, jax.random.uniform(key, (4, 8)),
               jax.random.uniform(key, (8, 4)),
               ScConfig(backend="array", nbit=64))
    snap = reg.snapshot()["counters"]
    assert snap["arch_sc_dot_calls_total"] == 1
    assert snap["arch_cycles_total"] > 0
    assert snap["arch_energy_pj_total"] > 0
    span = next(s for s in tr.spans if s.name == "sc.dispatch")
    assert span.attrs["arch_cycles"] == snap["arch_cycles_total"]
    assert span.attrs["arch_energy_pj"] > 0
    assert span.attrs["arch_shards"] == 1


def test_autotune_lookup_counters(global_obs):
    reg, tr = global_obs
    entry = {"block_m": 4, "block_n": 4, "block_k": 16, "lane_words": 8}
    cache = {autotune.cache_key(8, 32, 8, 256): entry}
    tile = autotune.get_tile(8, 32, 8, 256, cache=cache)     # hit
    assert tile == autotune.FusedTile(4, 4, 16, 8)
    autotune.get_tile(9, 32, 8, 256, cache=cache)            # miss
    autotune.get_attn_tile(8, 4, 8, 0, cache={})             # attn miss
    snap = reg.snapshot()["counters"]
    assert snap["sc_autotune_lookups_total{kind=matmul,result=hit}"] == 1
    assert snap["sc_autotune_lookups_total{kind=matmul,result=miss}"] == 1
    assert snap["sc_autotune_lookups_total{kind=attn,result=miss}"] == 1


# ---------------------------------------------------------------------------
# Serving integration: lifecycle counters + span accounting
# ---------------------------------------------------------------------------


def _requests(n, *, max_new=4):
    prompts = [[5, 9, 17, 3], [40, 2, 8, 30, 7, 11], [12, 33, 7],
               [3, 4, 5, 6, 7]]
    return [Request(rid=i, prompt=list(prompts[i % len(prompts)]),
                    max_new_tokens=max_new, temperature=0.0)
            for i in range(n)]


def _drain(params, cfg, reqs, *, slots=2, prefill_chunk=3, metrics=None,
           tracer=None, num_blocks=0):
    eng = PagedServingEngine(params, cfg, PagedServeConfig(
        slots=slots, max_len=32, block_size=4, prefill_chunk=prefill_chunk,
        num_blocks=num_blocks), metrics=metrics, tracer=tracer)
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    eng.close()
    return eng


@pytest.fixture(scope="module")
def serve_setup():
    cfg = _cfg()
    params = P.init_params(jax.random.PRNGKey(0), lm.lm_param_specs(cfg),
                           cfg.param_dtype)
    return cfg, params


def test_engine_emits_matching_spans_and_counters(serve_setup, tmp_path):
    """The acceptance assertion: drain a paged run with obs on, and the
    trace JSONL's span counts equal the engine's lifecycle events while
    the metrics exposition parses and carries the required series."""
    cfg, params = serve_setup
    metrics = obs.MetricsRegistry()
    tracer = obs.Tracer()
    reqs = _requests(3)
    chunk = 3
    eng = _drain(params, cfg, reqs, prefill_chunk=chunk, metrics=metrics,
                 tracer=tracer)
    n = len(reqs)
    counts = tracer.counts()
    # one submit/admit/finish event per request (no evictions here)
    assert counts["request.submit"] == n
    assert counts["request.admit"] == n
    assert counts["request.finish"] == n
    assert "request.evict" not in counts
    # one engine.tick span per tick, labeled prefill|decode, sums match
    assert counts["engine.tick"] == eng.ticks
    assert (metrics.value("serve_ticks_total", kind="prefill")
            + metrics.value("serve_ticks_total", kind="decode")) == eng.ticks
    # one prefill.chunk event per context chunk: ceil(plen / chunk) each
    want_chunks = sum(math.ceil(len(r.prompt) / chunk) for r in reqs)
    assert counts["prefill.chunk"] == want_chunks
    assert metrics.value("serve_prefill_tokens_total") == sum(
        len(r.prompt) for r in reqs)
    # counters match the finished requests
    tokens = sum(len(r.generated) for r in eng.finished)
    assert metrics.value("serve_tokens_generated_total") == tokens
    assert metrics.value("serve_requests_finished_total") == n
    assert metrics.value("serve_kv_blocks_allocated_total") == \
        metrics.value("serve_kv_blocks_freed_total") > 0
    assert metrics.value("serve_queue_depth") == 0
    assert metrics.value("serve_active_requests") == 0
    # tick spans carry kind/live/width attrs; a decode tick feeds one
    # position per live row and computes one per slot
    ticks = [s for s in tracer.spans if s.name == "engine.tick"]
    assert all(s.attrs["kind"] in ("prefill", "decode") for s in ticks)
    decode = [s for s in ticks if s.attrs["kind"] == "decode"]
    assert decode and all(s.attrs["fed"] == s.attrs["live"] for s in decode)
    assert all(s.attrs["width"] == 1 and s.attrs["computed"] == 2
               for s in decode)
    # the jit tick is excluded from the histogram but counted
    assert metrics.value("serve_decode_jit_ticks_total") == 1
    assert metrics.histogram("serve_decode_ms_per_token").count() == \
        len(decode) - 1
    # artifacts: exposition parses, snapshot is JSON, JSONL round-trips
    prom = tmp_path / "m.prom"
    prom.write_text(metrics.exposition())
    parsed = obs_report.load_snapshot(str(prom))
    assert parsed["counters"]["serve_requests_finished_total"] == n
    names = obs_report.metric_names(parsed)
    for required in ("serve_requests_submitted_total", "serve_ticks_total",
                     "serve_decode_ms_per_token", "serve_kv_blocks_free"):
        assert required in names
    jsonl = tracer.write_jsonl(str(tmp_path / "t.jsonl"))
    rows = obs.read_jsonl(jsonl)
    assert len(rows) == len(tracer.spans)
    assert len(obs.to_chrome(rows)["traceEvents"]) == len(rows) + 1


def test_engine_eviction_spans_and_counters(serve_setup):
    """A forced-eviction run emits request.evict events equal to the
    eviction counter, and admits = submits + evictions (resumes
    re-admit)."""
    cfg, params = serve_setup
    metrics = obs.MetricsRegistry()
    tracer = obs.Tracer()
    reqs = [Request(rid=i, prompt=[7 + i] * 8, max_new_tokens=12,
                    temperature=0.0) for i in range(2)]
    # 8 + 12 = 20 tokens/seq = 5 blocks each; 8 usable blocks force
    # eviction pressure between the two rows (cf. test_paged_attention's
    # eviction-resume test geometry)
    eng = _drain(params, cfg, reqs, prefill_chunk=4, metrics=metrics,
                 tracer=tracer, num_blocks=9)
    assert eng.evictions > 0
    counts = tracer.counts()
    assert counts["request.evict"] == eng.evictions
    assert metrics.value("serve_evictions_total") == eng.evictions
    assert metrics.value("serve_requests_admitted_total") == \
        len(reqs) + eng.evictions
    assert counts["request.admit"] == len(reqs) + eng.evictions
    resumed = [s for s in tracer.spans
               if s.name == "request.admit" and s.attrs["resumed"]]
    assert len(resumed) == eng.evictions


ENGINE_CHILDREN = ("engine.plan", "engine.copy", "engine.inputs",
                   "engine.step", "engine.sample", "engine.commit")


def _ticks_and_children(tracer):
    ticks = [s for s in tracer.spans if s.name == "engine.tick"]
    kids = {t.span_id: [] for t in ticks}
    for s in tracer.spans:
        if s.name in ENGINE_CHILDREN:
            kids[s.parent_id].append(s)      # KeyError: not under a tick
    return ticks, kids


def test_engine_tick_phases_nest_in_order(serve_setup):
    """Every tick of a drained run is one engine.tick span whose
    children are its host phases, in order, inside it and disjoint."""
    cfg, params = serve_setup
    tracer = obs.Tracer()
    eng = _drain(params, cfg, _requests(3), tracer=tracer)
    ticks, kids = _ticks_and_children(tracer)
    assert len(ticks) == eng.ticks > 0
    sampled = 0
    for t in ticks:
        phases = sorted(kids[t.span_id], key=lambda s: s.t0_ns)
        names = [s.name for s in phases]
        assert names == [n for n in ENGINE_CHILDREN if n in names]
        assert {"engine.plan", "engine.inputs", "engine.step",
                "engine.commit"} <= set(names)
        assert "engine.copy" not in names           # no prefix cache
        sampled += "engine.sample" in names
        assert phases[0].t0_ns >= t.t0_ns
        assert phases[-1].t0_ns + phases[-1].dur_ns <= t.t0_ns + t.dur_ns
        for a, b in zip(phases, phases[1:]):
            assert a.t0_ns + a.dur_ns <= b.t0_ns
    # every decode tick samples; a prefill tick samples when a prompt ends
    decode = sum(t.attrs["kind"] == "decode" for t in ticks)
    assert decode <= sampled <= len(ticks)
    # an idle engine records no tick
    assert not eng.step() and tracer.counts()["engine.tick"] == len(ticks)


def test_tick_attributes_sum_to_the_position_counters(serve_setup):
    """fed/computed/refed on the tick spans add up to the scheduler's
    counters; with no eviction every position is fed once."""
    cfg, params = serve_setup
    metrics, tracer = obs.MetricsRegistry(), obs.Tracer()
    eng = _drain(params, cfg, _requests(3), metrics=metrics, tracer=tracer)
    ticks = [s for s in tracer.spans if s.name == "engine.tick"]
    for attr, counter in (("fed", "serve_fed_positions_total"),
                          ("computed", "serve_step_positions_total"),
                          ("refed", "serve_refed_positions_total")):
        assert sum(t.attrs[attr] for t in ticks) == metrics.value(counter)
    # the last token of a request is sampled, never fed
    assert metrics.value("serve_fed_positions_total") == sum(
        len(r.prompt) + len(r.generated) - 1 for r in eng.finished)
    assert metrics.value("serve_step_positions_total") == sum(
        2 * t.attrs["width"] for t in ticks)
    assert metrics.value("serve_refed_positions_total") == 0


def test_evicted_rows_count_their_fed_positions_as_refed(serve_setup):
    """In the forced-eviction geometry, the re-fed positions are exactly
    those the evicted rows had fed, and every other position is fed
    once."""
    cfg, params = serve_setup
    metrics, tracer = obs.MetricsRegistry(), obs.Tracer()
    reqs = [Request(rid=i, prompt=[7 + i] * 8, max_new_tokens=12,
                    temperature=0.0) for i in range(2)]
    eng = _drain(params, cfg, reqs, prefill_chunk=4, metrics=metrics,
                 tracer=tracer, num_blocks=9)
    evicts = [s for s in tracer.spans if s.name == "request.evict"]
    assert len(evicts) == eng.evictions > 0
    refed = metrics.value("serve_refed_positions_total")
    assert refed == sum(s.attrs["fed"] for s in evicts) > 0
    assert metrics.value("serve_fed_positions_total") - refed == sum(
        len(r.prompt) + len(r.generated) - 1 for r in eng.finished)


def test_engine_without_a_tracer_opens_no_profiler_annotation(
        serve_setup, monkeypatch):
    """The default engine (NULL_TRACER) constructs no TraceAnnotation;
    the same run with an enabled tracer does."""
    from jax import profiler
    cfg, params = serve_setup
    made = []

    class Counting(profiler.TraceAnnotation):
        def __init__(self, name, **kwargs):
            made.append(name)
            super().__init__(name, **kwargs)

    monkeypatch.setattr(profiler, "TraceAnnotation", Counting)
    eng = _drain(params, cfg, _requests(2))
    assert eng.tracer is obs.NULL_TRACER and made == []
    _drain(params, cfg, _requests(2), tracer=obs.Tracer())
    assert "engine.tick" in made and "engine.step" in made


def test_engine_step_is_jitted_under_its_name(serve_setup):
    """The trace names the step ``jit_decode_paged``, as the benchmark's
    step readers expect."""
    cfg, params = serve_setup
    eng = _drain(params, cfg, [])
    table = jnp.zeros((2, 8), jnp.int32)
    text = eng._step_fn.lower(
        params, eng.pages, table, jnp.zeros((2, 1), jnp.int32),
        jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.int32)).as_text()
    assert text.startswith("module @jit_decode_paged")


@pytest.mark.parametrize("max_new,expect_none", [(2, True), (3, True),
                                                 (4, False)])
def test_decode_latency_edge_cases(serve_setup, max_new, expect_none):
    """max_new=N -> N-1 width-1 decode ticks (the chunk-aligned prompt
    prefills in one full-width tick), first dropped as the jit tick: 0 or
    1 recorded samples must yield None, 2+ the percentile dict."""
    cfg, params = serve_setup
    reqs = [Request(rid=0, prompt=[12, 33, 7], max_new_tokens=max_new,
                    temperature=0.0)]
    eng = _drain(params, cfg, reqs, slots=1)
    recorded = eng.metrics.histogram("serve_decode_ms_per_token").count()
    assert recorded == max_new - 2
    lat = eng.decode_latency_ms()
    if expect_none:
        assert lat is None
    else:
        assert set(lat) == {"decode_p50_ms", "decode_p95_ms"}
        assert 0 < lat["decode_p50_ms"] <= lat["decode_p95_ms"] * (1 + 1e-9)


def test_decode_latency_zero_ticks():
    """An engine that never decoded reports None (zero-sample guard)."""
    cfg = _cfg()
    params = P.init_params(jax.random.PRNGKey(0), lm.lm_param_specs(cfg),
                           cfg.param_dtype)
    eng = PagedServingEngine(params, cfg, PagedServeConfig(
        slots=1, max_len=32, block_size=4, prefill_chunk=3))
    assert eng.decode_latency_ms() is None
    eng.close()


def test_engines_default_to_private_registries(serve_setup):
    """Two engines must not mix series: each owns its registry unless the
    caller passes a shared one."""
    cfg, params = serve_setup
    a = _drain(params, cfg, _requests(1))
    b = _drain(params, cfg, _requests(2))
    assert a.metrics is not b.metrics
    assert a.metrics.value("serve_requests_finished_total") == 1
    assert b.metrics.value("serve_requests_finished_total") == 2


# ---------------------------------------------------------------------------
# Fleet-health view (ft.supervisor over the registry)
# ---------------------------------------------------------------------------


def test_engine_health_reads_registry(serve_setup):
    cfg, params = serve_setup
    eng = _drain(params, cfg, _requests(2))
    h = supervisor.engine_health(eng.metrics)
    assert h.finished == 2 and h.errors == 0
    assert h.ticks > 0 and h.error_rate == 0.0
    assert h.queue_depth == 0 and h.active_requests == 0
    snap = eng.health_snapshot()
    assert snap["finished"] == 2 and snap["error_rate"] == 0.0


def test_engine_health_fresh_registry_is_healthy():
    h = supervisor.engine_health(obs.MetricsRegistry())
    assert h == supervisor.EngineHealth()
    assert not supervisor.HealthMonitor().observe(h)


def test_health_monitor_error_rate_and_backlog_patience():
    mon = supervisor.HealthMonitor(max_error_rate=0.1, max_queue_depth=4,
                                   patience=2)
    ok = supervisor.EngineHealth(ticks=10, errors=0, error_rate=0.0,
                                 queue_depth=2)
    assert not mon.observe(ok)
    bad = supervisor.EngineHealth(ticks=10, errors=5, error_rate=0.5)
    assert mon.observe(bad) and mon.events[-1][0] == "error_rate"
    # one hot tick is load...
    backlog = supervisor.EngineHealth(queue_depth=9)
    assert not mon.observe(backlog)
    # ...a sustained one is a stall
    assert mon.observe(backlog) and mon.events[-1][0] == "queue_backlog"
    # recovery resets the streak
    assert not mon.observe(ok)
    assert not mon.observe(backlog)


def test_health_monitor_observe_registry():
    reg = obs.MetricsRegistry()
    reg.counter("serve_ticks_total").inc(10, kind="decode")
    reg.counter("serve_errors_total").inc(3)
    mon = supervisor.HealthMonitor(max_error_rate=0.1)
    assert mon.observe_registry(reg)


# ---------------------------------------------------------------------------
# tools/obs_report.py CLI
# ---------------------------------------------------------------------------


def _snap_file(tmp_path, name, counters, gauges=None):
    p = tmp_path / name
    p.write_text(json.dumps({"counters": counters, "gauges": gauges or {},
                             "histograms": {}}))
    return str(p)


def test_obs_report_require_missing_fails(tmp_path, capsys):
    p = _snap_file(tmp_path, "m.json", {"a_total": 1})
    assert obs_report.main([p, "--require", "a_total"]) == 0
    assert obs_report.main([p, "--require", "b_total"]) == 1
    assert "b_total" in capsys.readouterr().err


def test_obs_report_require_strips_labels(tmp_path):
    p = _snap_file(tmp_path, "m.json", {"ticks_total{kind=decode}": 3})
    assert obs_report.main([p, "--require", "ticks_total"]) == 0


def test_obs_report_diff(tmp_path, capsys):
    a = _snap_file(tmp_path, "a.json", {"n_total": 2}, {"depth": 1})
    b = _snap_file(tmp_path, "b.json", {"n_total": 5, "new_total": 1},
                   {"depth": 0})
    assert obs_report.main([b, a]) == 0
    out = capsys.readouterr().out
    assert "2 -> 5" in out and "(+3)" in out
    assert "new_total" in out and "new (1)" in out
    assert "1 -> 0" in out


def test_obs_report_chrome_cli(tmp_path):
    tr = obs.Tracer()
    with tr.span("tick"):
        pass
    jsonl = tr.write_jsonl(str(tmp_path / "t.jsonl"))
    out = str(tmp_path / "t.json")
    assert obs_report.main(["--chrome", jsonl, "-o", out]) == 0
    payload = json.load(open(out))
    assert any(e.get("name") == "tick" for e in payload["traceEvents"])


def test_obs_report_cli_subprocess(tmp_path):
    """The tool runs as a script (the CI smoke job invokes it that way)."""
    reg = obs.MetricsRegistry()
    reg.counter("serve_requests_finished_total").inc(4)
    p = tmp_path / "m.prom"
    p.write_text(reg.exposition())
    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "obs_report.py")
    res = subprocess.run(
        [sys.executable, tool, str(p), "--require",
         "serve_requests_finished_total"],
        capture_output=True, text=True)
    assert res.returncode == 0, res.stderr
    assert "serve_requests_finished_total" in res.stdout
