"""The fused SC engine + its autotuner.

The load-bearing property is BIT equality: ``pallas_fused`` must produce
the same floats as ``pallas_bitexact`` for the same key (shared
counter-based stream, exact integer accumulation), for every operand
grid, for ragged shapes, for per-row keys, and regardless of what tile
the autotuner picked.  The autotuner itself is pure performance state:
cache hits, misses, malformed entries, and version bumps may change
wall-clock, never bits.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sc
from repro.configs import get_smoke_config
from repro.models import layers
from repro.sc import autotune
from repro.launch.mesh import make_mesh

_NBIT = 64      # 2 packed words per product: fast but fully exercised


def _xw(key, m, k, n):
    kx, kw = jax.random.split(key)
    return (jax.random.normal(kx, (m, k), jnp.float32),
            jax.random.normal(kw, (k, n), jnp.float32))


# ---------------------------------------------------------------------------
# bit equality with the packed three-stage engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "operand_bits,quantize",
    [(4, True), (6, True), (8, True), (10, True), (16, True), (10, False)],
    ids=["4", "6", "8", "10", "16", "10-unquantized"])
@pytest.mark.parametrize("m,k,n", [(8, 32, 8), (5, 17, 3), (1, 9, 13)])
def test_fused_bit_equals_packed(key, operand_bits, quantize, m, k, n):
    """Same key => same bits as pallas_bitexact, across operand grids and
    ragged (non-block-multiple) shapes.  The fused ladder skips the slices
    a quantized grid leaves dead (none at 16 bits or unquantized); the
    packed path draws all 16, so this is the witness that they are."""
    x, w = _xw(key, m, k, n)
    kw = dict(nbit=_NBIT, operand_bits=operand_bits, quantize=quantize)
    yb = sc.sc_dot(key, x, w,
                   sc.ScConfig(backend="pallas_bitexact", **kw))
    yf = sc.sc_dot(key, x, w, sc.ScConfig(backend="pallas_fused", **kw))
    np.testing.assert_array_equal(np.asarray(yb), np.asarray(yf))


@pytest.mark.parametrize("levels,quantize,start", [
    (1024, True, 6), (16, True, 12), (65536, True, 0), (1024, False, 0),
    (1000, True, 0)])
def test_ladder_start(levels, quantize, start):
    from repro.kernels import sc_fused
    assert sc_fused.ladder_start(levels, quantize) == start


@pytest.mark.parametrize("lane_words", [1, 4])
def test_word_counts_live_slices_equal_full_ladder(lane_words):
    """On random quantized slabs (three (8, 128) slabs stacked) the ladder
    from ``ladder_start`` pop-counts exactly what the full 16-slice
    ladder does."""
    from repro.kernels import sc_fused
    levels = 1 << sc.ScConfig().operand_bits
    start = sc_fused.ladder_start(levels, True)
    assert start > 0
    rng = np.random.default_rng(lane_words)
    shape = (3 * sc_fused.SUBLANES, sc_fused.LANES)

    def u32():
        return jnp.asarray(rng.integers(0, 2 ** 32, size=shape,
                                        dtype=np.uint64), jnp.uint32)

    def fx16():
        return sc_fused.encode_fx16(
            jnp.asarray(rng.random(shape), jnp.float32), levels, True)

    kx, ky, pid, px, pw = (u32(), u32()), (u32(), u32()), u32(), fx16(), \
        fx16()
    assert not np.any(np.asarray(px | pw) & ((1 << start) - 1))
    counts = jax.jit(sc_fused.word_counts,
                     static_argnames=("nwords", "lane_words", "start"))
    full, live = (np.asarray(counts(kx, ky, pid, px, pw, nwords=8,
                                    lane_words=lane_words, start=s))
                  for s in (0, start))
    assert full.any()
    np.testing.assert_array_equal(full, live)


def test_fused_differs_across_keys(key):
    """Sanity: the stream actually depends on the key."""
    x, w = _xw(key, 4, 16, 4)
    cfg = sc.ScConfig(backend="pallas_fused", nbit=_NBIT)
    y1 = sc.sc_dot(jax.random.PRNGKey(1), x, w, cfg)
    y2 = sc.sc_dot(jax.random.PRNGKey(2), x, w, cfg)
    assert float(jnp.abs(y1 - y2).max()) > 0


def test_fused_unbiased_estimate(key):
    """The fused engine estimates x @ w with zero-centered error."""
    x, w = _xw(key, 4, 32, 4)
    cfg = sc.ScConfig(backend="pallas_fused", nbit=256)
    outs = jnp.stack([sc.sc_dot(k_, x, w, cfg)
                      for k_ in jax.random.split(key, 48)])
    exact = np.asarray(x @ w)
    sigma = np.asarray(outs.std(axis=0))
    tol = 5 * sigma / np.sqrt(48) + 0.02 * np.abs(exact).max()
    assert (np.abs(np.asarray(outs.mean(0)) - exact) < tol).mean() > 0.9


def test_fused_tile_choice_never_changes_bits(key):
    """Outputs are invariant to the autotuned tiling — the property that
    makes the cache safe to regenerate on any machine."""
    x, w = _xw(key, 6, 24, 5)
    cfg = sc.ScConfig(backend="pallas_fused", nbit=_NBIT)
    base = sc.sc_dot(key, x, w, cfg)
    from repro.kernels import sc_fused
    from repro.sc import ctr_rng, encoding
    scx = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scw = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
    kx, ky = jax.random.split(key)
    outs = []
    for tile in (autotune.FusedTile(4, 4, 16, 1),
                 autotune.FusedTile(8, 8, 32, 2)):
        spx = encoding.pad_to(encoding.pad_to(x / scx, tile.block_m, 0),
                              tile.block_k, 1)
        spw = encoding.pad_to(encoding.pad_to(w / scw, tile.block_k, 0),
                              tile.block_n, 1)
        keys = jnp.broadcast_to(jnp.concatenate(
            [ctr_rng.raw_key(kx), ctr_rng.raw_key(ky)])[None],
            (spx.shape[0], 4))
        total = sc_fused.sc_fused_popcount(
            keys, spx, spw, k_orig=24, n_orig=5, nbit=_NBIT, levels=1024,
            quantize=True, **tile.kwargs())
        outs.append(total[:6, :5].astype(jnp.float32) / _NBIT * (scx * scw))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(base))


# ---------------------------------------------------------------------------
# per-row keys (the serve engine's batch-invariance path)
# ---------------------------------------------------------------------------


def test_rows_mode_equals_per_row_single_calls(key):
    """sc_dot_rows row i == sc_dot on row i alone (bits AND scale), so
    outputs are invariant to batch composition."""
    m, k, n = 5, 24, 6
    x, w = _xw(key, m, k, n)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        key, jnp.arange(m, dtype=jnp.uint32))
    cfg = sc.ScConfig(backend="pallas_fused", nbit=_NBIT)
    rows = sc.sc_dot_rows(keys, x, w, cfg)
    singles = jnp.concatenate(
        [sc.sc_dot(keys[i], x[i:i + 1], w, cfg) for i in range(m)])
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(singles))
    # shuffling the batch permutes, never changes, each row's output
    perm = jnp.array([3, 0, 4, 1, 2])
    shuffled = sc.sc_dot_rows(keys[perm], x[perm], w, cfg)
    np.testing.assert_array_equal(np.asarray(shuffled),
                                  np.asarray(rows[perm]))


def test_rows_mode_vmap_fallback_unchanged(key):
    """Backends without a native rows path fall back to the per-row vmap
    and still match their single-key calls."""
    m, k, n = 4, 16, 4
    x, w = _xw(key, m, k, n)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        key, jnp.arange(m, dtype=jnp.uint32))
    cfg = sc.ScConfig(backend="moment", nbit=_NBIT)
    rows = sc.sc_dot_rows(keys, x, w, cfg)
    singles = jnp.concatenate(
        [sc.sc_dot(keys[i], x[i:i + 1], w, cfg) for i in range(m)])
    np.testing.assert_allclose(np.asarray(rows), np.asarray(singles),
                               rtol=1e-6, atol=1e-6)


def test_rows_mode_straight_through_gradients(key):
    m, k, n = 4, 16, 4
    x, w = _xw(key, m, k, n)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        key, jnp.arange(m, dtype=jnp.uint32))
    cfg = sc.ScConfig(backend="pallas_fused", nbit=_NBIT)

    def loss(x_, w_):
        return jnp.sum(sc.sc_dot_rows(keys, x_, w_, cfg) ** 2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    y = sc.sc_dot_rows(keys, x, w, cfg)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(2 * (y @ w.T)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(2 * (x.T @ y)),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# dense() fast-path routing
# ---------------------------------------------------------------------------


def test_fast_backend_mapping():
    assert sc.fast_backend("pallas_bitexact", 1024) == "pallas_fused"
    assert sc.fast_backend("pallas_bitexact", 48) == "pallas_bitexact"
    assert sc.fast_backend("moment", 1024) == "moment"
    assert sc.fast_backend("exact") == "exact"
    assert "pallas_fused" in sc.available_backends()


def test_dense_upgrades_bitexact_to_fused(key):
    """dense(sc_backend='pallas_bitexact') routes through the fused engine
    and — because the two are bit-identical — matches a direct
    pallas_fused sc_dot call, single-key and per-row-key alike."""
    cfg = get_smoke_config("paper-sc").replace(
        sc_backend="pallas_bitexact", sc_nbit=_NBIT,
        param_dtype=jnp.float32, act_dtype=jnp.float32)
    x = jax.random.normal(key, (3, 16), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 8), jnp.float32)
    y = layers.dense(x, w, cfg, key=key)
    direct = sc.sc_dot(key, x, w,
                       sc.ScConfig(backend="pallas_fused", nbit=_NBIT))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(direct))
    # per-row keys (the paged serve path): row i sees keys[i] only
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        key, jnp.arange(3, dtype=jnp.uint32))
    y_rows = layers.dense(x, w, cfg, key=keys)
    direct_rows = sc.sc_dot_rows(
        keys, x, w, sc.ScConfig(backend="pallas_fused", nbit=_NBIT))
    np.testing.assert_array_equal(np.asarray(y_rows),
                                  np.asarray(direct_rows))


# ---------------------------------------------------------------------------
# autotuner: cache semantics (never numerics)
# ---------------------------------------------------------------------------


def test_heuristic_fallback_on_cache_miss():
    tile = autotune.get_tile(13, 40, 7, 1024, cache={})
    assert tile == autotune.heuristic_tile(13, 40, 7, 1024)
    # deterministic: same signature, same tile
    assert tile == autotune.get_tile(13, 40, 7, 1024, cache={})
    # blocks stay VMEM-bounded
    assert autotune.tile_vmem_bytes(tile) <= autotune._VMEM_BLOCK_BYTES


def test_cache_hit_returns_stored_tile(tmp_path):
    path = str(tmp_path / "cache.json")
    stored = autotune.FusedTile(4, 8, 16, 2)
    entry = dict(stored.kwargs())
    entry["wall_us"] = 12.5          # extra fields tolerated
    autotune.save_cache({autotune.cache_key(8, 32, 8, 1024): entry}, path)
    cache = autotune.load_cache(path)
    assert autotune.get_tile(8, 32, 8, 1024, cache=cache) == stored
    # a different signature in the same cache still falls back
    assert autotune.get_tile(8, 32, 8, 512, cache=cache) == \
        autotune.heuristic_tile(8, 32, 8, 512)


def test_cache_version_bump_invalidates(tmp_path):
    path = str(tmp_path / "cache.json")
    entry = dict(autotune.FusedTile(4, 8, 16, 2).kwargs())
    with open(path, "w") as f:
        json.dump({"version": autotune.CACHE_VERSION + 1,
                   "entries": {autotune.cache_key(8, 32, 8, 1024): entry}},
                  f)
    assert autotune.load_cache(path) == {}      # stale table ignored
    with open(path, "w") as f:
        json.dump({"version": autotune.CACHE_VERSION,
                   "entries": {autotune.cache_key(8, 32, 8, 1024): entry}},
                  f)
    assert autotune.load_cache(path) != {}      # current version applies


def test_malformed_cache_and_entries_fall_back(tmp_path):
    path = str(tmp_path / "cache.json")
    with open(path, "w") as f:
        f.write("{not json")
    assert autotune.load_cache(path) == {}
    assert autotune.load_cache(str(tmp_path / "absent.json")) == {}
    bad = {autotune.cache_key(8, 32, 8, 1024): {"block_m": "huge"}}
    assert autotune.get_tile(8, 32, 8, 1024, cache=bad) == \
        autotune.heuristic_tile(8, 32, 8, 1024)
    # non-positive blocks would zero the kernel grid: heuristic, not crash
    zero = {autotune.cache_key(8, 32, 8, 1024): dict(
        block_m=0, block_n=8, block_k=32, lane_words=16)}
    assert autotune.get_tile(8, 32, 8, 1024, cache=zero) == \
        autotune.heuristic_tile(8, 32, 8, 1024)


def test_cache_hit_vs_miss_same_bits(key, tmp_path, monkeypatch):
    """THE determinism contract: a cache entry (hit) and no entry (miss,
    heuristic) produce bitwise identical sc_dot outputs."""
    m, k, n = 6, 20, 4
    x, w = _xw(key, m, k, n)
    cfg = sc.ScConfig(backend="pallas_fused", nbit=_NBIT)
    monkeypatch.setenv(autotune._CACHE_ENV,
                       str(tmp_path / "absent.json"))
    autotune.reset_cache()
    try:
        miss = sc.sc_dot(key, x, w, cfg)        # heuristic tile
        path = str(tmp_path / "cache.json")
        tile = autotune.FusedTile(4, 4, 4, 1)   # deliberately different
        assert tile != autotune.heuristic_tile(m, k, n, _NBIT)
        autotune.save_cache(
            {autotune.cache_key(m, k, n, _NBIT): tile.kwargs()}, path)
        monkeypatch.setenv(autotune._CACHE_ENV, path)
        autotune.reset_cache()
        assert autotune.get_tile(m, k, n, _NBIT) == tile    # really a hit
        hit = sc.sc_dot(key, x, w, cfg)
        np.testing.assert_array_equal(np.asarray(miss), np.asarray(hit))
    finally:
        autotune.reset_cache()


def test_shipped_cache_loads_and_is_current_version():
    """The repo ships a valid, version-current autotune table."""
    assert os.path.exists(autotune.DEFAULT_CACHE_PATH)
    with open(autotune.DEFAULT_CACHE_PATH) as f:
        payload = json.load(f)
    assert payload["version"] == autotune.CACHE_VERSION
    entries = autotune.load_cache(autotune.DEFAULT_CACHE_PATH)
    assert entries, "shipped cache must carry measured entries"
    for key_, entry in entries.items():
        tile = autotune.FusedTile(
            block_m=int(entry["block_m"]), block_n=int(entry["block_n"]),
            block_k=int(entry["block_k"]),
            lane_words=int(entry["lane_words"]))
        assert autotune.tile_vmem_bytes(tile) <= \
            autotune._VMEM_BLOCK_BYTES, key_


# ---------------------------------------------------------------------------
# sharding: trivial mesh reproduces sc_dot bit-for-bit
# ---------------------------------------------------------------------------


def test_fused_sharded_trivial_mesh_bit_equal(key):
    """On a 1-device mesh every axis drops and sc_dot_sharded must equal
    sc_dot exactly (same key, same bits) — the multi-device equivalence
    runs in tests/_sharded_subprocess.py."""
    mesh = make_mesh((1, 1), ("data", "model"))
    x, w = _xw(key, 8, 32, 8)
    cfg = sc.ScConfig(backend="pallas_fused", nbit=_NBIT)
    y_ref = sc.sc_dot(key, x, w, cfg)
    y_sh = sc.sc_dot_sharded(key, x, w, cfg, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_sh))
