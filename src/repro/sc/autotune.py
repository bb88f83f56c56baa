"""Tile-size autotuner for the fused SC engine (``pallas_fused``).

The fused kernel's wall-clock is dominated by how its (block_m, block_n,
block_k, lane_words) tiling trades grid-step overhead against per-tile
working-set size — and the best point depends on the call shape.  This
module owns that choice:

* a **versioned on-disk cache** (``autotune_cache.json``, shipped with
  the repo) maps ``(M, K, N, nbit, dtype)`` to a measured-best
  :class:`FusedTile`; ``tools/autotune.py`` refreshes it;
* a **deterministic heuristic** (:func:`heuristic_tile`) answers cache
  misses, so cold shapes still run with a sane tiling and the lookup is
  a pure function of the call signature;
* the tuner itself (:func:`tune_shape`) times candidate tiles through
  the real kernel entry point.

Crucially the tile choice can NEVER change results: the kernel draws
every stochastic word from the global counter-based stream
(``sc/ctr_rng.py``), so outputs are bitwise invariant to the tiling —
the cache is a pure performance table, safe to regenerate on any
machine (asserted in ``tests/test_sc_fused.py``).

Cache format (``CACHE_VERSION`` bumps invalidate the whole file)::

    {"version": 1,
     "entries": {"8x32x8|nbit=1024|dtype=float32":
                 {"block_m": 8, "block_n": 8, "block_k": 32,
                  "lane_words": 16, "wall_us": 1234.5}}}
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

from repro import obs

CACHE_VERSION = 1
DEFAULT_CACHE_PATH = os.path.join(os.path.dirname(__file__),
                                  "autotune_cache.json")
_CACHE_ENV = "REPRO_SC_AUTOTUNE_CACHE"

# per-step uint32 working-set cap (words) of the SC paged-attention
# kernel's (block_q, block_size, head_dim, lane_words) Bernoulli draws.
_MAX_TILE_WORDS = 1 << 16

# VMEM the fused matmul's pipelined blocks may take: double-buffered x^T,
# w and output tiles plus the int32 accumulator, padded to the (8, 128)
# tiling — half of the 16 MiB scoped VMEM a v5e kernel gets by default.
_VMEM_BLOCK_BYTES = 8 << 20
# largest contraction block: K longer than this steps over the grid
_MAX_BLOCK_K = 1024


@dataclasses.dataclass(frozen=True)
class FusedTile:
    """One fused-kernel tiling: matmul blocks + RNG words per inner step.

    ``lane_words`` packed 32-bit words (= 32·lane_words stochastic cells
    per product) are drawn per unrolled step of the kernel's word loop;
    larger values amortize loop overhead at the cost of a bigger kernel
    body.  The kernel aligns the blocks to the TPU tiling
    (``kernels/sc_fused.py:aligned_blocks``).
    """

    block_m: int = 8
    block_n: int = 8
    block_k: int = 32
    lane_words: int = 16

    def kwargs(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class AttnTile:
    """One fused paged-attention tiling (``kernels/paged_attention.py``).

    ``block_q`` query rows per grid step; ``lane_words`` packed 32-bit
    RNG words per Horner sweep of the SC-sampled QK^T (deterministic
    entries carry ``lane_words = 1`` as a placeholder — no rng drawn).
    Like the matmul tiles, the choice can never change bits: every
    logit's pop-count total is computed whole within one grid step from
    globally-addressed counters.
    """

    block_q: int = 8
    lane_words: int = 16

    def kwargs(self) -> dict:
        return dataclasses.asdict(self)


def cache_key(m: int, k: int, n: int, nbit: int,
              dtype: str = "float32") -> str:
    return f"{m}x{k}x{n}|nbit={nbit}|dtype={dtype}"


def attn_cache_key(rows: int, block_size: int, head_dim: int, nbit: int,
                   dtype: str = "float32") -> str:
    """``attn`` kernel-kind key: (query rows, kv block, head dim, nbit).

    ``rows = group * chunk_width`` is the kernel's flattened query-row
    axis per (batch, kv-head) slice; ``nbit = 0`` marks the
    deterministic (non-SC) QK^T variant.  The kind prefix keeps the
    attention entries disjoint from the matmul keys in the same
    versioned file.
    """
    return (f"attn|{rows}x{block_size}x{head_dim}|nbit={nbit}"
            f"|dtype={dtype}")


def load_cache(path: str | None = None) -> dict:
    """Entries of the on-disk cache; {} when absent, invalid, or stale.

    A ``version`` mismatch (``CACHE_VERSION`` bump) invalidates the whole
    file — stale tables from older kernel generations are never applied.
    """
    path = path or os.environ.get(_CACHE_ENV) or DEFAULT_CACHE_PATH
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return {}
    if payload.get("version") != CACHE_VERSION:
        return {}
    entries = payload.get("entries")
    return entries if isinstance(entries, dict) else {}


def save_cache(entries: dict, path: str | None = None) -> str:
    path = path or os.environ.get(_CACHE_ENV) or DEFAULT_CACHE_PATH
    with open(path, "w") as f:
        json.dump({"version": CACHE_VERSION, "entries": entries}, f,
                  indent=2, sort_keys=True)
        f.write("\n")
    return path


_CACHE: dict | None = None


def _cached_entries() -> dict:
    global _CACHE
    if _CACHE is None:
        _CACHE = load_cache()
    return _CACHE


def reset_cache() -> None:
    """Drop the in-process cache (tests / after tools/autotune.py runs)."""
    global _CACHE
    _CACHE = None


def _note_lookup(kind: str, result: str, tile) -> None:
    """Telemetry for one tile lookup: a hit/miss counter in the global
    registry (disabled by default) plus the chosen tile folded into the
    innermost open trace span — under an instrumented run that is the
    ``sc.dispatch`` span of the fused backend that asked."""
    reg = obs.default_registry()
    if reg.enabled:
        reg.counter(
            "sc_autotune_lookups_total",
            "tile-cache lookups by the fused backends (hit = stored "
            "measured tile, miss = deterministic heuristic)").inc(
                kind=kind, result=result)
    tr = obs.current_tracer()
    if tr is not None and tr.enabled:
        tr.attr(sc_autotune=result,
                sc_tile=str(dataclasses.astuple(tile)))


def _pow2_cover(dim: int, cap: int) -> int:
    """Smallest power of two >= dim, clamped to cap (operands pad up)."""
    p = 1
    while p < dim and p < cap:
        p *= 2
    return p


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def tile_vmem_bytes(tile: FusedTile) -> int:
    """VMEM bytes a fused-matmul tile's blocks take (see
    ``_VMEM_BLOCK_BYTES``)."""
    x = _round_up(tile.block_k, 8) * _round_up(tile.block_m, 128)
    w = _round_up(tile.block_k, 8) * _round_up(tile.block_n, 128)
    out = _round_up(tile.block_m, 8) * _round_up(tile.block_n, 128)
    return 4 * (2 * (x + w + out) + out)


def _lane_block(n: int) -> int:
    """Output-column block: all of a narrow N, else the lane multiple up
    to 512 that pads N least (ties go to the wider block)."""
    if n <= 128:
        return n
    return min((512, 384, 256, 128), key=lambda c: (_round_up(n, c), -c))


def heuristic_tile(m: int, k: int, n: int, nbit: int) -> FusedTile:
    """Deterministic cache-miss fallback, aligned to the TPU tiling.

    Rows: all of them up to 128 (the kernel walks them one at a time).
    Columns: lane-dense blocks that pad N least.  Contraction: as few
    sublane-aligned blocks as ``_MAX_BLOCK_K`` allows, so the integer
    accumulator loops few grid steps and K pads by under 8 per block.
    One RNG word per unrolled inner step keeps the kernel body small.
    """
    del nbit
    bm = min(m, 128)
    bn = _lane_block(n)
    nk = -(-k // _MAX_BLOCK_K)
    bk = _round_up(-(-k // nk), 8)
    return FusedTile(block_m=bm, block_n=bn, block_k=bk, lane_words=1)


def get_tile(m: int, k: int, n: int, nbit: int, dtype: str = "float32",
             cache: dict | None = None) -> FusedTile:
    """Cache-then-heuristic lookup — THE tile the fused backend runs with.

    Pure function of (shape, nbit, dtype, cache contents): a cache hit
    returns the stored tile verbatim; a miss falls back to
    :func:`heuristic_tile`.  Either way the kernel's outputs are
    identical (tiling never changes the counter-based draw).
    """
    entries = cache if cache is not None else _cached_entries()
    entry = entries.get(cache_key(m, k, n, nbit, dtype))
    if entry is not None:
        try:
            tile = FusedTile(
                block_m=int(entry["block_m"]), block_n=int(entry["block_n"]),
                block_k=int(entry["block_k"]),
                lane_words=int(entry["lane_words"]))
            if min(dataclasses.astuple(tile)) >= 1:
                _note_lookup("matmul", "hit", tile)
                return tile
        except (KeyError, TypeError, ValueError):
            pass                     # malformed entry -> heuristic
    tile = heuristic_tile(m, k, n, nbit)
    _note_lookup("matmul", "miss", tile)
    return tile


def heuristic_attn_tile(rows: int, block_size: int, head_dim: int,
                        nbit: int) -> AttnTile:
    """Deterministic cache-miss fallback for the paged-attention kernel.

    Deterministic QK^T (``nbit <= 0``) draws no stochastic words, so the
    only knob is ``block_q``.  The SC variant unrolls one RNG word per
    step of its word loop (the smallest kernel body, quickest to
    compile) and bounds its per-step (block_q, block_size, head_dim)
    Bernoulli working set by the VMEM cap.
    """
    bq = _pow2_cover(rows, 8)
    if nbit <= 0:
        return AttnTile(block_q=bq, lane_words=1)
    while bq * block_size * head_dim > _MAX_TILE_WORDS and bq > 1:
        bq //= 2
    return AttnTile(block_q=bq, lane_words=1)


def get_attn_tile(rows: int, block_size: int, head_dim: int, nbit: int,
                  dtype: str = "float32",
                  cache: dict | None = None) -> AttnTile:
    """Cache-then-heuristic lookup for the fused paged-attention kernel.

    Same contract as :func:`get_tile`: pure function of the call
    signature and cache contents, and the returned tiling can never
    change the kernel's bits — only its wall-clock.
    """
    entries = cache if cache is not None else _cached_entries()
    entry = entries.get(attn_cache_key(rows, block_size, head_dim, nbit,
                                       dtype))
    if entry is not None:
        try:
            tile = AttnTile(block_q=int(entry["block_q"]),
                            lane_words=int(entry["lane_words"]))
            if min(dataclasses.astuple(tile)) >= 1:
                _note_lookup("attn", "hit", tile)
                return tile
        except (KeyError, TypeError, ValueError):
            pass                     # malformed entry -> heuristic
    tile = heuristic_attn_tile(rows, block_size, head_dim, nbit)
    _note_lookup("attn", "miss", tile)
    return tile


def candidate_tiles(m: int, k: int, n: int, nbit: int) -> list:
    """The tuner's search space for one call shape (heuristic included).

    Deliberately small: each candidate pays a fresh kernel compile.
    Column blocks are lane multiples (or all of a narrow N), contraction
    blocks sublane multiples, and every candidate fits the VMEM budget.
    """
    nwords = max(1, nbit // 32)
    bm = min(m, 128)
    bns = {n} if n <= 128 else {c for c in (128, 256, 512)
                                if c <= _round_up(n, 128)}
    bks = {min(_round_up(k, 8), c) for c in (256, 512, 1024)}
    cands = [FusedTile(bm, bn, bk, lane)
             for bn in bns for bk in bks
             for lane in {min(nwords, c) for c in (1, 2, 4)}]
    cands = [t for t in cands if tile_vmem_bytes(t) <= _VMEM_BLOCK_BYTES]
    cands.append(heuristic_tile(m, k, n, nbit))
    return sorted(set(cands), key=lambda t: dataclasses.astuple(t))


def measure_tile(m: int, k: int, n: int, nbit: int, tile: FusedTile, *,
                 operand_bits: int = 10, iters: int = 3,
                 warmup: int = 1, seed: int = 0) -> float:
    """Median wall-clock µs of the fused kernel under ``tile``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import sc_fused
    from repro.sc import ctr_rng

    key = jax.random.PRNGKey(seed)
    kx, kw, kk = jax.random.split(key, 3)
    x = jax.random.uniform(kx, (m, k), jnp.float32, -1.0, 1.0)
    w = jax.random.uniform(kw, (k, n), jnp.float32, -1.0, 1.0)
    kx2, ky2 = jax.random.split(kk)
    keys = jnp.broadcast_to(
        jnp.concatenate([ctr_rng.raw_key(kx2), ctr_rng.raw_key(ky2)])[None],
        (m, 4))

    def run():
        return sc_fused.sc_fused_popcount(
            keys, x, w, k_orig=k, n_orig=n, nbit=nbit,
            levels=1 << operand_bits, quantize=True,
            **tile.kwargs()).block_until_ready()

    for _ in range(warmup):
        run()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run()
        ts.append((time.perf_counter() - t0) * 1e6)
    ts.sort()
    return ts[len(ts) // 2]


def tune_shape(m: int, k: int, n: int, nbit: int, *,
               candidates: list | None = None, iters: int = 3,
               verbose: bool = False) -> tuple:
    """Time every candidate tile; returns ``(best_tile, best_us, table)``."""
    cands = candidates if candidates is not None else candidate_tiles(
        m, k, n, nbit)
    table = []
    for tile in cands:
        us = measure_tile(m, k, n, nbit, tile, iters=iters)
        table.append((tile, us))
        if verbose:
            print(f"  {dataclasses.astuple(tile)!s:<22} {us:10.1f} us")
    best_tile, best_us = min(table, key=lambda tu: tu[1])
    return best_tile, best_us, table


def candidate_attn_tiles(rows: int, block_size: int, head_dim: int,
                         nbit: int) -> list:
    """Search space for one paged-attention call shape (small on purpose)."""
    cands = []
    if nbit <= 0:
        for bq in {_pow2_cover(rows, c) for c in (4, 8, 16, 32)}:
            cands.append(AttnTile(block_q=bq, lane_words=1))
    else:
        nwords = max(1, nbit // 32)
        for bq in {_pow2_cover(rows, c) for c in (4, 8, 16)}:
            for lane in {min(nwords, c) for c in (8, 16, 32)}:
                if bq * block_size * head_dim * lane <= _MAX_TILE_WORDS:
                    cands.append(AttnTile(block_q=bq, lane_words=lane))
    cands.append(heuristic_attn_tile(rows, block_size, head_dim, nbit))
    return sorted(set(cands), key=lambda t: dataclasses.astuple(t))


def measure_attn_tile(rows: int, block_size: int, head_dim: int, nbit: int,
                      tile: AttnTile, *, num_pages: int = 8,
                      operand_bits: int = 10, iters: int = 3,
                      warmup: int = 1, seed: int = 0) -> float:
    """Median wall-clock µs of the fused paged-attention kernel.

    ``rows`` is treated as a single-request, single-kv-head row axis
    (chunk width ``rows``, group 1) — the per-step work the kernel does
    is identical for any (group, chunk) split of the same row count.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import paged_attention
    from repro.sc import ctr_rng

    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.uniform(kq, (1, rows, 1, head_dim), jnp.float32,
                           -1.0, 1.0)
    k_pages = jax.random.uniform(
        kk, (num_pages, 1, block_size, head_dim), jnp.float32, -1.0, 1.0)
    v_pages = jax.random.uniform(
        kv, (num_pages, 1, block_size, head_dim), jnp.float32, -1.0, 1.0)
    table = jnp.arange(num_pages, dtype=jnp.int32)[None]
    lengths = jnp.array([num_pages * block_size - rows], jnp.int32)
    keys = jnp.broadcast_to(ctr_rng.raw_key(key)[None, None],
                            (1, rows, 2))

    if nbit <= 0:
        def run():
            return paged_attention.paged_attention_fused(
                q, k_pages, v_pages, table, lengths,
                block_q=tile.block_q).block_until_ready()
    else:
        def run():
            return paged_attention.paged_attention_fused_sc(
                keys, q, k_pages, v_pages, table, lengths, nbit=nbit,
                operand_bits=operand_bits, block_q=tile.block_q,
                lane_words=tile.lane_words).block_until_ready()

    for _ in range(warmup):
        run()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run()
        ts.append((time.perf_counter() - t0) * 1e6)
    ts.sort()
    return ts[len(ts) // 2]


def tune_attn_shape(rows: int, block_size: int, head_dim: int, nbit: int, *,
                    candidates: list | None = None, iters: int = 3,
                    verbose: bool = False) -> tuple:
    """Time every candidate attention tile; ``(best, best_us, table)``."""
    cands = candidates if candidates is not None else candidate_attn_tiles(
        rows, block_size, head_dim, nbit)
    table = []
    for tile in cands:
        us = measure_attn_tile(rows, block_size, head_dim, nbit, tile,
                               iters=iters)
        table.append((tile, us))
        if verbose:
            print(f"  {dataclasses.astuple(tile)!s:<22} {us:10.1f} us")
    best_tile, best_us = min(table, key=lambda tu: tu[1])
    return best_tile, best_us, table
