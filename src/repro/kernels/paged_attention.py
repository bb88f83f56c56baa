"""Pallas kernel: fused paged attention for the decode hot path.

The paged serve path (``models/attention.py:paged_attention_block``)
historically ran block-table gather -> QK^T -> softmax -> V accumulation
as separate XLA ops, materializing every row's full gathered cache view
per tick.  This kernel collapses the sequence into ONE ``pallas_call``
per layer, flash-attention style:

* **block-table gather in-kernel** — the block table and fill lengths
  ride in as scalar-prefetch operands (SMEM), and the K/V ``index_map``
  reads the table, so grid step ``(row, kv head, q tile, j)`` DMAs just
  the one ``(block_size, head_dim)`` page slice that table entry ``j``
  names for that kv head — the pool is laid out ``(P, kv, bs, hd)`` so
  that slice is contiguous — and the (b, nb*bs, kv, hd) gathered view is
  never materialized;
* **online softmax** — a running (max, denominator, accumulator) triple
  lives in VMEM scratch across the KV-block grid axis (the same
  recurrence as ``models/attention.py:blockwise_attention``), so peak
  memory per step is one (block_q, block_size) logits tile;
* **masking identical to the unfused path** — kv position ``t`` is live
  for chunk row ``i`` of request ``r`` iff ``t <= lengths[r] + i``
  (causal within the chunk plus the fill mask), exactly
  ``chunk_decode_attention``'s predicate, so fused and unfused outputs
  agree to float tolerance and greedy-decoded tokens are identical
  (``tests/test_paged_attention.py``).

:func:`mla_paged_attention` runs latent attention's absorbed form on its
own launcher, :func:`_launch_spans`: one latent pool row per position
serves every head (kvh = 1, a query group of all heads), its lanes are
the keys and its first ``kv_lora_rank`` lanes the values, and the scale
is passed in.  Each grid step reads a span of :func:`span_pages` pages
(128 positions, a lane-dense logits tile) and the walk stops at each
row's last live position, ``lengths[r] + sc - 1``: past it every page
index clamps to the last live page, so no DMA is issued and no compute
runs.  The GQA and SC kernels keep :func:`_launch`'s one page a step
over all of ``max_len``.

The SC variant (:func:`paged_attention_fused_sc`) replaces the exact
QK^T with the paper's stochastic MUL: operands quantize onto the DTC
grid in-kernel (``kernels/sc_fused.py:encode_fx16``), Bernoulli cells
come from the Horner bit-ladder, and logits are signed pop-count totals.
Every uniform word draws from ``sc/ctr_rng.py``'s pinned Threefry-2x32
stream with the QUERY TOKEN's key (folded from its request key and
absolute position upstream) and counter

    c0 = (t_abs * n_heads + head) * head_dim + d,   c1 = s * nwords + w

so a logit's bits depend only on (request key, query position, kv
position, head, d) — never on batch composition, chunk boundaries, KV
block size, or eviction/resume.  :func:`sc_qk_logits_host` is the
host-side twin (same jnp body, bit equality by construction) the
invariance tests pin against.

Tile selection (``block_q`` rows per grid step, ``lane_words`` RNG words
per unrolled step) routes through ``sc/autotune.py``'s versioned cache
under the ``attn`` kernel kind, with a deterministic heuristic fallback;
``block_q`` rounds up to a sublane multiple.  Tiling never changes bits:
each logit's pop-count total is computed whole within one grid step from
globally-addressed counters.  The kernels run compiled on a TPU and in
the Pallas interpreter on the CPU (:func:`repro.kernels.interpret_mode`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels.sc_fused import encode_fx16, ladder_start, word_counts
from repro.kernels.sc_mul import LANE_BITS
from repro.sc import autotune, ctr_rng

NEG_INF = -1e30  # matches models/attention.py
_DENOM_GUARD = 1e-30  # matches blockwise_attention's divide guard
_SCALE_GUARD = 1e-30  # matches sc/encoding.py's max-abs clamp
_HIGHEST = jax.lax.Precision.HIGHEST


def _scale(hd: int):
    # the selfsame construction as chunk_decode_attention, so the fused
    # logits match the unfused path bit-for-bit before the softmax
    return 1.0 / jnp.sqrt(hd).astype(jnp.float32)


def split_keys4(keys):
    """Per-token raw ``(..., 2)`` keys -> ``(..., 4)`` operand key words.

    The same x/y operand-stream split the fused SC matmul uses
    (``sc/backends.py:pallas_fused_rows``): ``jax.random.split`` each
    token key, query stream takes the first half, key stream the second.
    """
    raw = ctr_rng.raw_key(keys)
    flat = raw.reshape(-1, 2)
    split = jax.vmap(jax.random.split)(flat)  # (N, 2, 2)
    keys4 = jnp.concatenate([split[:, 0], split[:, 1]], axis=-1)
    return keys4.reshape(raw.shape[:-1] + (4,)).astype(jnp.uint32)


def _online_softmax_step(logits, v_blk, m_ref, d_ref, a_ref):
    """One flash-attention update of the (m, denom, acc) VMEM carry."""
    m_prev = m_ref[...]  # (bq, 1)
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    m_ref[...] = m_new
    d_ref[...] = d_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    a_ref[...] = a_ref[...] * alpha + jnp.dot(
        p, v_blk, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _mask(logits, length, *, j, sc, block_size, block_q):
    """``chunk_decode_attention``'s predicate: t <= lengths[r] + i."""
    shape = (block_q, block_size)
    t_idx = j * block_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    row = pl.program_id(2) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0
    )
    q_pos = length + row % sc
    return jnp.where(t_idx <= q_pos, logits, NEG_INF)


def _init_carry(m_ref, d_ref, a_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    d_ref[...] = jnp.zeros_like(d_ref)
    a_ref[...] = jnp.zeros_like(a_ref)


def _emit(o_ref, d_ref, a_ref):
    out = a_ref[...] / jnp.maximum(d_ref[...], _DENOM_GUARD)
    o_ref[0, 0] = out.astype(o_ref.dtype)


def _paged_attn_kernel(
    bt_ref,
    len_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    d_ref,
    a_ref,
    *,
    sc: int,
    block_size: int,
    nb: int,
    block_q: int,
):
    """Deterministic fused step: one page, QK^T, online softmax."""
    del bt_ref  # consumed by the K/V index maps
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        _init_carry(m_ref, d_ref, a_ref)

    k_blk = k_ref[0, 0].astype(jnp.float32)  # (bs, hd)
    v_blk = v_ref[0, 0].astype(jnp.float32)  # (bs, hd)
    q_blk = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
    logits = jax.lax.dot_general(
        q_blk, k_blk, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32) * _scale(q_blk.shape[-1])
    logits = _mask(logits, len_ref[pl.program_id(0)], j=j, sc=sc,
                   block_size=block_size, block_q=block_q)
    _online_softmax_step(logits, v_blk, m_ref, d_ref, a_ref)

    @pl.when(j == nb - 1)
    def _():
        _emit(o_ref, d_ref, a_ref)


def _last_live(length, sc: int):
    """A row's last live kv position: chunk row ``sc - 1`` sits at
    ``length + sc - 1`` and :func:`_mask` keeps every ``t`` up to it."""
    return length + sc - 1


def _mla_span_kernel(
    bt_ref,
    len_ref,
    q_ref,
    *refs,
    sc: int,
    block_size: int,
    span: int,
    n_spans: int,
    block_q: int,
    scale: float,
    dv: int,
):
    """Absorbed-MLA span step: ``span`` latent pages, stacked in table
    order, are the keys (all their lanes) and the values (their first
    ``dv`` lanes) of one (block_q, span * block_size) logits tile.  Spans
    that start past the row's last live position run nothing."""
    del bt_ref  # consumed by the page index maps
    pages, (o_ref, m_ref, d_ref, a_ref) = refs[:span], refs[span:]
    j = pl.program_id(3)
    length = len_ref[pl.program_id(0)]
    positions = span * block_size
    # _mask's predicate, t <= lengths[r] + i, with the query positions
    # taken out here: the interpreter reads no program id inside pl.when
    row = pl.program_id(2) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    q_pos = length + row % sc

    @pl.when(j == 0)
    def _():
        _init_carry(m_ref, d_ref, a_ref)

    @pl.when(j * positions <= _last_live(length, sc))
    def _():
        kv = jnp.concatenate([pg[0, 0] for pg in pages], axis=0)
        # scores on the operands' own dtype: bfloat16 products are exact
        # in float32, so this equals an upcast at HIGHEST in one MXU pass
        exact = _HIGHEST if kv.dtype == jnp.float32 else None
        logits = jax.lax.dot_general(
            q_ref[0, 0], kv, (((1,), (1,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32) * scale
        t_idx = j * positions + jax.lax.broadcasted_iota(
            jnp.int32, (1, positions), 1)
        logits = jnp.where(t_idx <= q_pos, logits, NEG_INF)
        _online_softmax_step(logits, kv[:, :dv].astype(jnp.float32), m_ref,
                             d_ref, a_ref)

    @pl.when(j == n_spans - 1)
    def _():
        _emit(o_ref, d_ref, a_ref)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "nbit", "levels", "quantize", "lane"))
def _sc_logits(q_blk, k_blk, keys4, t_abs, head, *, n_heads, nbit,
               levels, quantize, lane):
    """SC-sampled QK^T logits tile from exact q/k tiles (f32 in/out).

    q_blk: (bq, hd) query rows; k_blk: (T, hd) cache rows at absolute
    positions ``t_abs`` (1, T) int32; keys4: (bq, 4) uint32 per-row
    operand key words; head: (bq, 1) int32 flat query head per row.
    Returns (bq, T) pre-mask logits.  Walks the T cache rows one at a
    time so every vector stays (bq, hd) with rows on sublanes; row
    selection is an exact masked sum.  The pop-count core
    (``sc_fused.word_counts``) sums integers, so ``lane`` can never
    change the result.
    """
    bq, hd = q_blk.shape
    n_kv = k_blk.shape[0]
    nwords = nbit // LANE_BITS
    scq = jnp.maximum(jnp.max(jnp.abs(q_blk), axis=1, keepdims=True),
                      _SCALE_GUARD)
    sck = jnp.maximum(jnp.max(jnp.abs(k_blk), axis=1, keepdims=True),
                      _SCALE_GUARD)
    fxq = encode_fx16(jnp.abs(q_blk) / scq, levels, quantize)
    fxk = jax.lax.bitcast_convert_type(
        encode_fx16(jnp.abs(k_blk) / sck, levels, quantize), jnp.int32)
    sgq = jnp.sign(q_blk).astype(jnp.int32)
    sgk = jnp.sign(k_blk).astype(jnp.int32)
    kq = (keys4[:, 0:1], keys4[:, 1:2])
    kk = (keys4[:, 2:3], keys4[:, 3:4])
    d_idx = jax.lax.broadcasted_iota(jnp.int32, (1, hd), 1)
    kv_row = jax.lax.broadcasted_iota(jnp.int32, (n_kv, hd), 0)
    kv_row1 = jax.lax.broadcasted_iota(jnp.int32, (n_kv, 1), 0)
    kv_lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_kv), 1)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (bq, n_kv), 1)

    def column(t, logits):
        sel = kv_row == t
        pk = jax.lax.bitcast_convert_type(
            jnp.sum(jnp.where(sel, fxk, 0), axis=0, keepdims=True),
            jnp.uint32)                                      # (1, hd)
        sk = jnp.sum(jnp.where(sel, sgk, 0), axis=0, keepdims=True)
        sck_t = jnp.sum(jnp.where(kv_row1 == t, sck, 0.0), axis=0,
                        keepdims=True)                       # (1, 1)
        ta = jnp.sum(jnp.where(kv_lane == t, t_abs, 0), axis=1,
                     keepdims=True)                          # (1, 1)
        c0 = jax.lax.bitcast_convert_type(
            (ta * n_heads + head) * hd + d_idx, jnp.uint32)  # (bq, hd)
        counts = word_counts(
            kq, kk, c0, fxq, jnp.broadcast_to(pk, (bq, hd)),
            nwords=nwords, lane_words=lane,
            start=ladder_start(levels, quantize))
        total = jnp.sum(sgq * sk * counts, axis=1, keepdims=True)
        est = total.astype(jnp.float32) / jnp.float32(nbit) * scq * sck_t
        return jnp.where(out_lane == t, est * _scale(hd), logits)

    return jax.lax.fori_loop(0, n_kv, column,
                             jnp.zeros((bq, n_kv), jnp.float32))


def _paged_attn_sc_kernel(
    bt_ref,
    len_ref,
    q_ref,
    keys_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    d_ref,
    a_ref,
    *,
    sc: int,
    block_size: int,
    nb: int,
    block_q: int,
    n_heads: int,
    group: int,
    nbit: int,
    levels: int,
    quantize: bool,
    lane_words: int,
):
    """SC-sampled fused step: same gather and online softmax, but the
    QK^T tile is the paper's stochastic MUL drawn from each query
    token's pinned counter stream."""
    del bt_ref  # consumed by the K/V index maps
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        _init_carry(m_ref, d_ref, a_ref)

    k_blk = k_ref[0, 0].astype(jnp.float32)  # (bs, hd)
    v_blk = v_ref[0, 0].astype(jnp.float32)  # (bs, hd)
    q_blk = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)

    # the query's identity rides in its KEY, the kv side in the counter,
    # so the draw survives any batch/chunk/block-size/eviction reshuffle
    row = pl.program_id(2) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    head = pl.program_id(1) * group + row // sc
    t_abs = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    logits = _sc_logits(
        q_blk, k_blk, keys_ref[0], t_abs, head, n_heads=n_heads,
        nbit=nbit, levels=levels, quantize=quantize, lane=lane_words)
    logits = _mask(logits, len_ref[pl.program_id(0)], j=j, sc=sc,
                   block_size=block_size, block_q=block_q)
    _online_softmax_step(logits, v_blk, m_ref, d_ref, a_ref)

    @pl.when(j == nb - 1)
    def _():
        _emit(o_ref, d_ref, a_ref)


def _rows_layout(q, kvh: int):
    """(b, sc, h, hd) queries -> (b, kvh, g*sc, hd) kernel rows.

    Row ``r`` of a (batch, kv-head) slice holds query head
    ``kvh_index * g + r // sc`` at chunk offset ``r % sc`` — the same
    grouping as ``models/attention.py:_grouped``.
    """
    b, sc, h, hd = q.shape
    g = h // kvh
    qg = q.reshape(b, sc, kvh, g, hd).transpose(0, 2, 3, 1, 4)
    return qg.reshape(b, kvh, g * sc, hd)


def _rows_unlayout(out, *, sc: int, h: int):
    """Inverse of :func:`_rows_layout` (after slicing off row padding)."""
    b, kvh, rows, hd = out.shape
    g = rows // sc
    out = out.reshape(b, kvh, g, sc, hd).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, sc, h, hd)


def _launch(kernel, *, name, q_rows, block_table, lengths, extra, pools,
            block_q, out_width=None):
    """One fused-attention ``pallas_call`` over grid (b, kv, q tiles, nb),
    named ``name`` in the compiled program and the profiler's trace.

    q_rows: (b, kvh, rows_p, hd) kernel rows; extra: per-row operands of
    shape (b, rows_p, w) blocked alongside the query tile; pools: the
    (P, kvh, bs, w) page pools the kernel reads, K then V (one pool where
    values are lanes of the key page).  The block table (flattened) and
    lengths are scalar-prefetched; each pool's step DMAs the
    (block_size, w) slice of the page the table names.  The output and
    accumulator are ``out_width`` wide (default: the query width).
    """
    b, kvh, rows_p, hd = q_rows.shape
    bs = pools[0].shape[2]
    nb = block_table.shape[1]
    dv = out_width or hd

    def page(i, h_, qi, j, bt, ln):
        return (bt[i * nb + j], h_, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, rows_p // block_q, nb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda i, h_, qi, j, bt, ln: (i, h_, qi, 0)),
            *[pl.BlockSpec((1, block_q, e.shape[-1]),
                           lambda i, h_, qi, j, bt, ln: (i, qi, 0))
              for e in extra],
            *[pl.BlockSpec((1, 1, bs, pool.shape[-1]), page)
              for pool in pools],
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dv),
                               lambda i, h_, qi, j, bt, ln: (i, h_, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows_p, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=kernels.interpret_mode(),
        name=name,
    )(block_table.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      q_rows, *extra, *pools)


SPAN_POSITIONS = 128  # lanes of one logits tile
_SPAN_VMEM = 4 << 20  # double-buffered span pages + the step's f32 tiles


def span_pages(block_size: int, width: int, itemsize: int, block_q: int,
               nb: int) -> int:
    """Pages one grid step of :func:`_launch_spans` reads: enough for
    :data:`SPAN_POSITIONS` positions, no more than the table's ``nb``,
    and halved while the pages (double-buffered, ``width`` lanes of
    ``itemsize`` bytes) and the (block_q, positions) float32 logits and
    probabilities would pass the VMEM budget."""
    span = max(1, min(SPAN_POSITIONS // block_size, nb))

    def vmem(s):
        return s * block_size * (2 * width * itemsize + 2 * block_q * 4)

    while span > 1 and vmem(span) > _SPAN_VMEM:
        span //= 2
    return span


def pages_walked(lengths, sc: int, block_size: int, nb: int) -> int:
    """Pages one layer's :func:`_launch_spans` walk reads, on the host:
    row ``r`` reads its table through its last live page, ``min(nb,
    ceil((lengths[r] + sc) / block_size))`` pages, and skips the rest."""
    live = -(-(np.asarray(lengths, np.int64) + sc) // block_size)
    return int(np.minimum(live, nb).sum())


def _launch_spans(kernel, *, name, q_rows, block_table, lengths, pools,
                  span, sc, block_q, out_width=None):
    """One fused-attention ``pallas_call`` over grid (b, kv, q tiles,
    spans of ``span`` pages), named ``name``; the walk stops at each
    row's fill.

    q_rows: (b, kvh, rows_p, hd) kernel rows; pools: the (P, kvh, bs, w)
    page pools, each passed ``span`` times so that Pallas pipelines one
    page slot per operand.  Slot ``s`` of span ``j`` reads table entry
    ``min(j * span + s, last)``, ``last`` the row's last live page
    (:func:`_last_live`, at most ``nb - 1``): a slot past the fill
    repeats the last live page, and a step whose pages all repeat the
    previous step's issues no DMA.  The kernel gets, after the query
    tile, ``span`` page refs per pool in pool order, the output and the
    (max, denominator, accumulator) scratch, ``out_width`` wide
    (default: the query width).
    """
    b, kvh, rows_p, hd = q_rows.shape
    bs = pools[0].shape[2]
    nb = block_table.shape[1]
    dv = out_width or hd

    def page(s):
        def index(i, h_, qi, j, bt, ln):
            last = jnp.minimum(jax.lax.div(_last_live(ln[i], sc), bs),
                               nb - 1)
            return (bt[i * nb + jnp.minimum(j * span + s, last)], h_, 0, 0)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, rows_p // block_q, -(-nb // span)),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda i, h_, qi, j, bt, ln: (i, h_, qi, 0)),
            *[pl.BlockSpec((1, 1, bs, pool.shape[-1]), page(s))
              for pool in pools for s in range(span)],
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dv),
                               lambda i, h_, qi, j, bt, ln: (i, h_, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows_p, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=kernels.interpret_mode(),
        name=name,
    )(block_table.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      q_rows, *[pool for pool in pools for _ in range(span)])


def _row_tile(block_q: int) -> int:
    """Query rows per grid step, rounded up to a sublane multiple."""
    return -(-max(1, block_q) // 8) * 8


def paged_attention_fused(
    q,
    k_pages,
    v_pages,
    block_table,
    lengths,
    *,
    block_q: int = 0,
):
    """Fused paged attention, deterministic QK^T.

    q: (b, sc, h, hd) post-rope queries (chunk token i of row r sits at
    absolute position ``lengths[r] + i``, K/V already scattered);
    k/v_pages: (P, kvh, bs, hd) block pools; block_table: (b, nb);
    lengths: (b,) pre-chunk fill.  Returns (b, sc, h, hd) — the fused
    equivalent of ``chunk_decode_attention(q, paged_gather(k), ...)``.
    ``block_q = 0`` takes the row tile from the autotune cache
    (``attn`` kernel kind; heuristic on miss).
    """
    b, sc, h, hd = q.shape
    _, kvh, bs, _ = k_pages.shape
    nb = block_table.shape[1]
    rows = (h // kvh) * sc
    if block_q <= 0:
        block_q = autotune.get_attn_tile(rows, bs, hd, 0).block_q
    block_q = _row_tile(block_q)
    qr = _rows_layout(q, kvh)
    pad = (-rows) % block_q
    if pad:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kernel = functools.partial(
        _paged_attn_kernel, sc=sc, block_size=bs, nb=nb, block_q=block_q
    )
    out = _launch(kernel, name="paged_attention", q_rows=qr,
                  block_table=block_table, lengths=lengths, extra=(),
                  pools=(k_pages, v_pages), block_q=block_q)
    return _rows_unlayout(out[:, :, :rows], sc=sc, h=h).astype(q.dtype)


def mla_paged_attention(q, pool, block_table, lengths, *, dv: int,
                        scale: float, block_q: int = 0):
    """Absorbed latent attention (MLA) over a latent page pool.

    q: (b, sc, h, dk) absorbed queries, ``[q_nope·W_UK, rope(q_pe)]``;
    pool: (P, 1, bs, dk) latent pages, ``[c, rope(k_pe)]`` once per
    position for every head; block_table, lengths: as
    :func:`paged_attention_fused`.  An MQA of ``h`` query heads over one
    latent "KV head": scores are ``q·page`` times ``scale`` (the
    decompressed head's 1/sqrt(qk width), not 1/sqrt(dk)), values the
    first ``dv`` lanes of the same page.  Returns the latent outputs
    (b, sc, h, dv), which the caller takes up through W_UV.  Same
    masking as the GQA kernel; the walk reads :func:`span_pages` pages a
    grid step and stops at each row's fill (:func:`_launch_spans`), so
    table entries past a row's last live page are never read.
    """
    b, sc, h, dk = q.shape
    bs = pool.shape[2]
    nb = block_table.shape[1]
    rows = h * sc
    if block_q <= 0:
        block_q = autotune.get_attn_tile(rows, bs, dk, 0).block_q
    block_q = _row_tile(block_q)
    span = span_pages(bs, dk, pool.dtype.itemsize, block_q, nb)
    qr = _rows_layout(q, 1)
    pad = (-rows) % block_q
    if pad:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kernel = functools.partial(
        _mla_span_kernel, sc=sc, block_size=bs, span=span,
        n_spans=-(-nb // span), block_q=block_q, scale=float(scale), dv=dv)
    out = _launch_spans(kernel, name="mla_paged_attention", q_rows=qr,
                        block_table=block_table, lengths=lengths,
                        pools=(pool,), span=span, sc=sc, block_q=block_q,
                        out_width=dv)
    return _rows_unlayout(out[:, :, :rows], sc=sc, h=h).astype(q.dtype)


def paged_attention_fused_sc(
    keys,
    q,
    k_pages,
    v_pages,
    block_table,
    lengths,
    *,
    nbit: int,
    operand_bits: int = 10,
    quantize: bool = True,
    block_q: int = 0,
    lane_words: int = 0,
):
    """Fused paged attention with the SC-sampled QK^T.

    keys: (b, sc, 2) raw per-token keys — each already folded from its
    request key and ABSOLUTE position upstream (``lm.decode_paged``), so
    the stochastic logits a token draws are a function of (request key,
    position, head, kv position) alone.  Other operands as
    :func:`paged_attention_fused`.  ``block_q`` / ``lane_words`` = 0
    take the ``attn`` autotune entry; the tiling never changes bits.
    """
    b, sc, h, hd = q.shape
    _, kvh, bs, _ = k_pages.shape
    nb = block_table.shape[1]
    g = h // kvh
    rows = g * sc
    assert nbit % LANE_BITS == 0, "SC attention packs 32 cells per word"
    tile = autotune.get_attn_tile(rows, bs, hd, nbit)
    if block_q <= 0:
        block_q = tile.block_q
    block_q = _row_tile(block_q)
    if lane_words <= 0:
        lane_words = tile.lane_words
    nwords = nbit // LANE_BITS
    lane_words = max(1, min(lane_words, nwords))
    while nwords % lane_words:
        lane_words -= 1
    qr = _rows_layout(q, kvh)
    keys4 = split_keys4(keys)  # (b, sc, 4)
    rowk = jnp.broadcast_to(keys4[:, None], (b, g, sc, 4)).reshape(b, rows, 4)
    pad = (-rows) % block_q
    if pad:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, pad), (0, 0)))
        rowk = jnp.pad(rowk, ((0, 0), (0, pad), (0, 0)))
    kernel = functools.partial(
        _paged_attn_sc_kernel,
        sc=sc,
        block_size=bs,
        nb=nb,
        block_q=block_q,
        n_heads=h,
        group=g,
        nbit=nbit,
        levels=1 << operand_bits,
        quantize=quantize,
        lane_words=lane_words,
    )
    out = _launch(kernel, name="paged_attention_sc", q_rows=qr,
                  block_table=block_table, lengths=lengths, extra=(rowk,),
                  pools=(k_pages, v_pages), block_q=block_q)
    return _rows_unlayout(out[:, :, :rows], sc=sc, h=h).astype(q.dtype)


def sc_qk_logits_host(
    key,
    q_row,
    k_rows,
    t_abs,
    head: int,
    n_heads: int,
    *,
    nbit: int,
    operand_bits: int = 10,
    quantize: bool = True,
):
    """Host-side twin of the kernel's SC QK^T for ONE query token.

    key: raw (2,) token key; q_row: (hd,) post-rope query; k_rows:
    (T, hd) cache rows sitting at absolute positions ``t_abs`` (T,);
    ``head`` is the query's flat head index.  Same jnp body as the
    kernel (same counters, same Threefry, same Horner ladder), so the
    returned (T,) logits equal the kernel's pre-mask logits bit-for-bit
    by construction — the anchor the reproducibility tests pin.
    """
    keys4 = split_keys4(key[None])  # (1, 4)
    logits = _sc_logits(
        q_row[None].astype(jnp.float32),
        k_rows.astype(jnp.float32),
        keys4,
        jnp.asarray(t_abs, jnp.int32)[None],
        jnp.full((1, 1), head, jnp.int32),
        n_heads=n_heads,
        nbit=nbit,
        levels=1 << operand_bits,
        quantize=quantize,
        lane=max(1, nbit // LANE_BITS),
    )
    return logits[0]
