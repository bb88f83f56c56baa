"""Mesh-sharded SC substrate: rules resolution, shard accounting, and the
single-device degradations. Multi-device equivalence (8 simulated CPU
devices) runs in a subprocess so this process keeps the single real CPU
device (tests/_sharded_subprocess.py)."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import arch, sc
from repro.arch.accounting import merge_concurrent_reports, merge_reports
from repro.sharding import sc_shard_rules
from repro.launch.mesh import make_mesh

KEY = jax.random.PRNGKey(0)
X = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
W = jax.random.normal(jax.random.PRNGKey(2), (8, 5))


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# Rules resolution
# ---------------------------------------------------------------------------


def test_resolve_rules_drops_size_one_axes():
    r = sc.resolve_rules(_mesh11(), m=4, k=8)
    assert r.batch == () and r.contract == ()


def test_resolve_rules_drops_absent_axes():
    mesh = _mesh11()
    r = sc.resolve_rules(mesh, m=4, k=8,
                         rules=sc.ScShardRules(batch=("nope",),
                                               contract=("missing",)))
    assert r.batch == () and r.contract == ()


def test_shard_counts_trivial_mesh():
    assert sc.shard_counts(_mesh11(), 4, 8) == (1, 1)


def test_sc_shard_rules_adapts_to_mesh():
    rules = sc_shard_rules(_mesh11())
    assert rules.batch == ("data",)        # pod absent, dropped
    assert rules.contract == ("model",)


# ---------------------------------------------------------------------------
# Trivial-mesh equivalence: no live axis => exactly sc_dot, same bits
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["exact", "moment", "bitexact"])
def test_trivial_mesh_identical_bits(backend):
    cfg = sc.ScConfig(backend=backend, nbit=256)
    y_ref = sc.sc_dot(KEY, X, W, cfg)
    y_sh = sc.sc_dot_sharded(KEY, X, W, cfg, mesh=_mesh11())
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_sh))


def test_leading_dims_flatten_like_sc_dot():
    x3 = X.reshape(2, 2, 8)
    cfg = sc.ScConfig(backend="moment", nbit=1024)
    y_ref = sc.sc_dot(KEY, x3, W, cfg)
    y_sh = sc.sc_dot_sharded(KEY, x3, W, cfg, mesh=_mesh11())
    assert y_sh.shape == (2, 2, 5)
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_sh))


# ---------------------------------------------------------------------------
# Concurrent-shard accounting
# ---------------------------------------------------------------------------


def _report(m=32, k=256, n=64, nbit=1024):
    return arch.schedule_call(m, k, n, nbit).report


def test_merge_concurrent_empty():
    r = merge_concurrent_reports([])
    assert r.cycles == 0 and r.products == 0


def test_merge_concurrent_identical_shards():
    one = _report()
    merged = merge_concurrent_reports([one] * 8)
    assert merged.cycles == one.cycles              # makespan: slowest shard
    assert merged.products == 8 * one.products      # work adds
    assert merged.energy_pj == pytest.approx(8 * one.energy_pj)
    assert merged.subarray_util == pytest.approx(one.subarray_util)
    assert merged.cell_occupancy == pytest.approx(one.cell_occupancy)


def test_merge_concurrent_uneven_shards_idle_tail():
    fast, slow = _report(m=8), _report(m=64)
    merged = merge_concurrent_reports([fast, slow])
    assert merged.cycles == max(fast.cycles, slow.cycles)
    # the fast shard idles while the slow one finishes => combined
    # utilization below the slow shard's own
    assert merged.subarray_util < slow.subarray_util + 1e-12


def test_serial_vs_concurrent_merge():
    one = _report()
    serial = merge_reports([one] * 4)
    conc = merge_concurrent_reports([one] * 4)
    assert serial.cycles == 4 * conc.cycles
    assert serial.energy_pj == pytest.approx(conc.energy_pj)
    assert serial.products == conc.products


def test_callrecord_shard_stamp_and_effective_report():
    cfg = sc.ScConfig(backend="array", nbit=1024)
    with sc.shard_scope(4), arch.collect() as recs:
        sc.sc_dot(KEY, X, W, cfg)
    (rec,) = recs
    assert rec.shards == 4
    eff = rec.effective_report
    assert eff.products == 4 * rec.report.products
    assert eff.cycles == rec.report.cycles
    # collectors aggregate the effective (concurrency-aware) reports
    agg = arch.summarize(recs)["aggregate"]
    assert agg["products"] == eff.products
    assert rec.as_dict()["shards"] == 4


# ---------------------------------------------------------------------------
# Sharded workload pricing
# ---------------------------------------------------------------------------


def test_shard_site_ceil_division():
    s = arch.MatmulSite("mlp.wi", m=10, k=30, n=7, count=2)
    piece = arch.shard_site(s, data=4, model=8)
    assert (piece.m, piece.k, piece.n) == (3, 4, 7)
    assert piece.count == 2


def test_price_workload_sharded_degenerate_matches_unsharded():
    sites = [arch.MatmulSite("a", 32, 256, 64, 2)]
    _, t1 = arch.price_workload(sites, nbit=1024)
    _, t2 = arch.price_workload_sharded(sites, nbit=1024, data=1, model=1)
    assert t1 == t2


def test_price_workload_sharded_makespan_strictly_less():
    sites = [arch.MatmulSite("a", 32, 256, 64, 2)]
    _, t1 = arch.price_workload(sites, nbit=1024)
    _, t8 = arch.price_workload_sharded(sites, nbit=1024, data=2, model=4)
    assert t8.cycles < t1.cycles
    assert t8.products == t1.products
    assert t8.energy_pj == pytest.approx(t1.energy_pj)


# ---------------------------------------------------------------------------
# Multi-device equivalence (simulated 8-device mesh, subprocess)
# ---------------------------------------------------------------------------


def test_multidevice_sharded_equivalence():
    """Numerics + grads + arch overlap + serve engine on a forced
    8-device host platform (see tests/_sharded_subprocess.py)."""
    script = os.path.join(os.path.dirname(__file__),
                          "_sharded_subprocess.py")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(os.path.dirname(__file__), "..", "src"),
                    env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "ALL-SHARDED-OK" in proc.stdout
