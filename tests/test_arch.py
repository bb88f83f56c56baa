"""Array-level architecture simulator (repro.arch): spec/tiler/schedule/
accounting invariants, closed-form agreement, the registered ``array``
backend, trace collection, and the serve-engine hook."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import arch, sc
from repro.configs import get_smoke_config
from repro.core import costmodel as cm
from repro.models import lm, params as P
from repro.serve import Request, ServeConfig, ServingEngine

NBIT = 1024            # 2^10 — the paper's 10-bit evaluation point


# --------------------------------------------------------------------------
# Spec
# --------------------------------------------------------------------------


def test_spec_totals_and_mapping():
    s = arch.ArraySpec(banks=2, subarrays_per_bank=4, rows_per_subarray=8,
                       row_length=256)
    assert s.subarrays == 8 and s.rows == 64 and s.cells == 64 * 256
    assert s.rows_per_product(1024) == 4
    assert s.products_per_subarray(1024) == 2
    assert s.products_per_wave(1024) == 16


def test_spec_rejects_bad_geometry():
    with pytest.raises(ValueError, match="positive int"):
        arch.ArraySpec(banks=0)
    with pytest.raises(ValueError, match="cross-subarray"):
        arch.ArraySpec(rows_per_subarray=2).products_per_subarray(1024)


# --------------------------------------------------------------------------
# Tiler
# --------------------------------------------------------------------------


def test_tiler_conserves_products():
    plan = arch.tile_matmul(8, 32, 8, NBIT)
    assert plan.products == 8 * 32 * 8
    tiles = list(arch.iter_tiles(plan))
    assert sum(t.products for t in tiles) == plan.products
    assert sum(t.cells for t in tiles) == plan.cells_touched
    spec = plan.spec
    for t in tiles:
        assert t.rows <= spec.rows_per_subarray
        assert 0 <= t.bank < spec.banks
        assert 0 <= t.subarray < spec.subarrays_per_bank


def test_tiler_wave_split():
    spec = arch.ArraySpec(banks=1, subarrays_per_bank=2, rows_per_subarray=8)
    # 5 products at 4 rows each; 2 per subarray per wave, 4 per wave -> 2 waves
    plan = arch.tile_matmul(5, 1, 1, NBIT, spec)
    assert (plan.waves, plan.full_waves, plan.tail_products) == (2, 1, 1)
    assert plan.tail_subarrays == 1
    waves = {}
    for t in arch.iter_tiles(plan):
        waves.setdefault(t.wave, 0)
        waves[t.wave] += t.products
    assert waves == {0: 4, 1: 1}


def test_tiler_rejects_empty_dims():
    with pytest.raises(ValueError, match="positive"):
        arch.tile_matmul(0, 4, 4, NBIT)


def test_occupancy_full_when_wave_aligned():
    spec = arch.ArraySpec(banks=1, subarrays_per_bank=1, rows_per_subarray=4)
    plan = arch.tile_matmul(1, 1, 1, NBIT, spec)   # exactly fills the chip
    assert arch.occupancy(plan) == 1.0


# --------------------------------------------------------------------------
# Schedule + accounting vs the closed-form §V model
# --------------------------------------------------------------------------


def test_single_mul_trace_matches_closed_form_cycles():
    rec = arch.schedule_call(1, 1, 1, NBIT)
    assert rec.report.cycles == cm.cycles_scpim_apc(10)
    assert [c.op for c in rec.trace] == ["PRESET", "PULSE_X", "PULSE_Y",
                                         "READ", "POPCOUNT", "MERGE"]


def test_single_mul_trace_matches_closed_form_energy():
    rec = arch.schedule_call(1, 1, 1, NBIT)
    expect, _ = cm.energy_scpim(10, "apc")
    np.testing.assert_allclose(rec.report.energy_pj, expect, rtol=1e-12)


def test_trace_reproduces_headline_ratios():
    """Acceptance: ≈4x vs SC and ≈18x vs PIM emerge from the trace."""
    cycles = arch.schedule_call(1, 1, 1, NBIT).report.cycles
    assert 3.0 <= cm.cycles_sc(10) / cycles <= 5.0
    assert 15.0 <= cm.cycles_pim(8) / cycles <= 21.0


def test_no_merge_when_product_fits_one_row():
    rec = arch.schedule_call(1, 1, 1, 256)
    assert "MERGE" not in [c.op for c in rec.trace]
    assert rec.report.cycles == cm.cycles_scpim_apc(8)   # 2^8 = 256 bits


def test_waves_serialize_cycles():
    spec = arch.ArraySpec(banks=1, subarrays_per_bank=1, rows_per_subarray=4)
    one = arch.schedule_call(1, 1, 1, NBIT, spec).report.cycles
    three = arch.schedule_call(3, 1, 1, NBIT, spec).report.cycles
    assert three == 3 * one           # same subarray reused -> 3 full waves


def test_parallel_products_do_not_add_cycles():
    base = arch.schedule_call(1, 1, 1, NBIT).report
    wave = arch.schedule_call(4, 2, 4, NBIT).report    # still one wave
    assert wave.cycles == base.cycles
    assert wave.products == 32
    np.testing.assert_allclose(wave.energy_pj, 32 * base.energy_pj,
                               rtol=1e-12)


def test_schedule_rejects_row_length_mismatch():
    plan = arch.tile_matmul(1, 1, 1, NBIT,
                            arch.ArraySpec(row_length=128))
    with pytest.raises(ValueError, match="row_length"):
        arch.compile_schedule(plan, cm.DEFAULT_PARAMS)


def test_accounting_utilization_bounds():
    rep = arch.schedule_call(8, 32, 8, NBIT).report
    assert 0.0 < rep.subarray_util <= 1.0
    assert 0.0 < rep.cell_occupancy <= 1.0
    assert rep.cycles_by_op["READ"] > 0
    assert rep.energy_by_op["PRESET"] > rep.energy_by_op["POPCOUNT"]


def test_merge_reports_adds_cycles_and_energy():
    a = arch.schedule_call(1, 1, 1, NBIT).report
    merged = arch.merge_reports([a, a, a])
    assert merged.cycles == 3 * a.cycles
    np.testing.assert_allclose(merged.energy_pj, 3 * a.energy_pj)
    assert merged.products == 3 * a.products
    scaled = arch.scaled(a, 3)
    assert scaled.cycles == merged.cycles


def test_cost_params_sweep_changes_trace():
    slow = cm.CostParams(sa_read_cycles=8)
    plan = arch.tile_matmul(1, 1, 1, NBIT)
    base = arch.account(arch.compile_schedule(plan), plan.spec)
    swept = arch.account(arch.compile_schedule(plan, slow), plan.spec, slow)
    assert swept.cycles == base.cycles + 6


# --------------------------------------------------------------------------
# The registered backend
# --------------------------------------------------------------------------


def test_array_backend_registered_lazily():
    assert "array" in sc.available_backends()
    assert sc.get_backend("array") is not None


def test_array_backend_round_trip(key):
    x = jax.random.normal(key, (8, 32), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 8), jnp.float32)
    y = sc.sc_dot(key, x, w, sc.ScConfig(backend="array", nbit=NBIT))
    assert y.shape == (8, 8)
    assert bool(jnp.all(jnp.isfinite(y)))


def test_array_backend_mean_agrees_with_exact(key):
    """Acceptance: mean agrees with ``exact`` within sampling tolerance at
    n = 2^10 stochastic bits."""
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (4, 16), jnp.float32)
    w = jax.random.normal(kw, (16, 4), jnp.float32)
    cfg = sc.ScConfig(backend="array", nbit=NBIT)
    n_rep = 32
    outs = jax.vmap(lambda k_: sc.sc_dot(k_, x, w, cfg))(
        jax.random.split(key, n_rep))
    mean = np.asarray(outs.mean(axis=0))
    sigma = np.asarray(outs.std(axis=0))
    exact = np.asarray(x @ w)
    tol = 5 * sigma / np.sqrt(n_rep) + 0.02 * np.abs(exact).max()
    assert (np.abs(mean - exact) < tol).mean() > 0.9


def test_array_backend_straight_through_gradient(key):
    x = jax.random.normal(key, (4, 16), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 4), jnp.float32)
    cfg = sc.ScConfig(backend="array", nbit=NBIT)

    def loss(x_, w_):
        return jnp.sum(sc.sc_dot(key, x_, w_, cfg) ** 2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    y = sc.sc_dot(key, x, w, cfg)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(2 * (y @ w.T)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(2 * (x.T @ y)),
                               rtol=1e-4, atol=1e-4)


def test_array_backend_respects_ambient_spec(key):
    x = jax.random.normal(key, (2, 8), jnp.float32)
    w = jax.random.normal(key, (8, 2), jnp.float32)
    tiny = arch.ArraySpec(banks=1, subarrays_per_bank=1, rows_per_subarray=4)
    with arch.use_spec(tiny), arch.collect() as records:
        sc.sc_dot(key, x, w, sc.ScConfig(backend="array", nbit=NBIT))
    assert records[0].plan.spec == tiny
    assert records[0].plan.waves == 32      # 2*8*2 products, 1 per wave


def test_array_backend_validates_spec_even_untraced(key):
    x = jax.random.normal(key, (2, 8), jnp.float32)
    w = jax.random.normal(key, (8, 2), jnp.float32)
    bad = arch.ArraySpec(rows_per_subarray=1)
    with arch.use_spec(bad):
        with pytest.raises(ValueError, match="cross-subarray"):
            sc.sc_dot(key, x, w, sc.ScConfig(backend="array", nbit=NBIT))


# --------------------------------------------------------------------------
# Trace collection
# --------------------------------------------------------------------------


def test_collector_records_once_per_compiled_shape(key):
    cfg = sc.ScConfig(backend="array", nbit=256)
    x = jax.random.normal(key, (4, 8), jnp.float32)
    w = jax.random.normal(key, (8, 4), jnp.float32)
    f = jax.jit(lambda k_, x_, w_: sc.sc_dot(k_, x_, w_, cfg))
    with arch.collect() as records:
        for i in range(3):
            f(jax.random.fold_in(key, i), x, w).block_until_ready()
    assert len(records) == 1            # jit cache: one record per shape
    assert records[0].shape == (4, 8, 4)


def test_nested_collectors_both_hear(key):
    cfg = sc.ScConfig(backend="array", nbit=256)
    x = jnp.ones((2, 4), jnp.float32)
    w = jnp.ones((4, 2), jnp.float32)
    with arch.collect() as outer:
        with arch.collect() as inner:
            sc.sc_dot(key, x, w, cfg)
        sc.sc_dot(key, x, w, cfg)
    assert len(inner) == 1 and len(outer) == 2


def test_summarize_is_json_ready(key):
    with arch.collect() as records:
        sc.sc_dot(key, jnp.ones((2, 4)), jnp.ones((4, 2)),
                  sc.ScConfig(backend="array", nbit=256))
    import json
    s = arch.summarize(records, arch.DEFAULT_SPEC)
    json.dumps(s)                       # must not raise
    assert s["calls"] == 1
    assert s["aggregate"]["cycles"] > 0


# --------------------------------------------------------------------------
# Model stack + serve engine end-to-end
# --------------------------------------------------------------------------


def test_lm_forward_on_array_backend_traces_all_dense_sites(key):
    cfg = get_smoke_config("paper-sc").replace(
        sc_backend="array", param_dtype=jnp.float32, act_dtype=jnp.float32)
    params = P.init_params(key, lm.lm_param_specs(cfg), jnp.float32)
    toks = jax.random.randint(key, (1, 8), 2, cfg.vocab)
    with arch.collect() as records:
        logits = lm.forward(params, toks, cfg, rng=jax.random.PRNGKey(1))
    assert bool(jnp.all(jnp.isfinite(logits)))
    # one record per dense() site in the scanned block (logits head is
    # exact-path): wq wk wv wo + mlp wi wo
    assert len(records) == len(arch.dense_workload(cfg, 8))
    assert all(r.report.cycles > 0 for r in records)


def test_serve_engine_arch_trace_hook(key):
    cfg = get_smoke_config("paper-sc").replace(
        sc_backend="array", param_dtype=jnp.float32, act_dtype=jnp.float32)
    params = P.init_params(key, lm.lm_param_specs(cfg), jnp.float32)
    engine = ServingEngine(params, cfg, ServeConfig(slots=1, max_len=32),
                           collect_arch_trace=True)
    try:
        engine.submit(Request(rid=0, prompt=[3, 7, 11], max_new_tokens=2))
        finished = engine.run_until_drained()
        assert len(finished) == 1
        rep = engine.arch_report()
        assert rep is not None and rep.cycles > 0 and rep.energy_pj > 0
    finally:
        engine.close()
    assert engine.arch_collector not in arch.trace._LISTENERS


def test_serve_engine_without_hook_has_no_report(key):
    cfg = get_smoke_config("qwen2-0.5b").replace(
        param_dtype=jnp.float32, act_dtype=jnp.float32)
    params = P.init_params(key, lm.lm_param_specs(cfg), jnp.float32)
    engine = ServingEngine(params, cfg, ServeConfig(slots=1, max_len=32))
    assert engine.arch_report() is None
    engine.close()                      # no-op, must not raise


def test_serve_engine_hook_requires_array_backend(key):
    """collect_arch_trace on a non-array backend installs nothing (there
    would be no dispatches to hear) and leaves the listener list clean."""
    cfg = get_smoke_config("qwen2-0.5b").replace(
        param_dtype=jnp.float32, act_dtype=jnp.float32)
    params = P.init_params(key, lm.lm_param_specs(cfg), jnp.float32)
    before = list(arch.trace._LISTENERS)
    engine = ServingEngine(params, cfg, ServeConfig(slots=1, max_len=32),
                           collect_arch_trace=True)
    assert engine.arch_collector is None
    assert arch.trace._LISTENERS == before
    del engine                          # __del__ path must not raise


# --------------------------------------------------------------------------
# Workload extraction
# --------------------------------------------------------------------------


def test_dense_workload_covers_families():
    for arch_id in ("paper-sc", "qwen3-14b", "moonlight-16b-a3b",
                    "mamba2-370m", "zamba2-7b"):
        cfg = get_smoke_config(arch_id)
        sites = arch.dense_workload(cfg, tokens=16)
        assert sites, arch_id
        assert all(s.products > 0 for s in sites)


def test_dense_workload_hybrid_multiplicity_matches_lm():
    """Hybrid layer counts must come from the lm assembly, not a copy."""
    cfg = get_smoke_config("zamba2-7b")
    sites = {s.label: s for s in arch.dense_workload(cfg, tokens=4)}
    assert sites["ssm.wz"].count == lm.n_backbone_layers(cfg)
    assert sites["shared.attn.wq"].count == lm.n_shared_invocations(cfg)


def test_price_workload_totals_consistent():
    cfg = get_smoke_config("paper-sc")
    sites = arch.dense_workload(cfg, tokens=8)
    per_site, total = arch.price_workload(sites, NBIT)
    assert total.cycles == sum(r.cycles for _, r in per_site)
    assert total.products == sum(s.products for s in sites)
