"""Pallas TPU kernel: FULLY fused bit-exact SC matmul engine.

``pallas_bitexact`` (kernels/sc_mul.py) realizes the paper's packed MUL
faithfully but in three separate stages: the host encodes operands and
materializes the whole per-product uniform stream (O(M·K·N·nbit/32)
words through HBM), the kernel ANDs/pop-counts it, and the host reduces
over K.  This kernel collapses all of it into ONE ``pallas_call``:

* **operand-grid encoding** — tiles arrive as raw signed probabilities
  ``v / max|v|``; the LUT/DTC-grid quantization (§III-A) and the fx16
  bias-word conversion happen in-kernel, with bit-for-bit the formulas of
  ``sc/encoding.py``;
* **counter-based RNG draw** — every uniform word regenerates in-kernel
  from ``sc/ctr_rng.py``'s pinned Threefry-2x32 stream keyed by the
  *global* product coordinates, so the draw is independent of tile shape
  and identical to the stream ``pallas_bitexact`` materializes on the
  host (same key ⇒ same bits, whatever the autotuner picked);
* **MTJ write-probability thresholding** — the Horner bit-ladder of
  ``kernels/sc_mul.py`` turns uniform words into packed Bernoulli cells,
  32 per lane word (the row-parallel stochastic write).  The ladder
  starts at the first slice a bias word can set (``ladder_start``): on
  a quantized 2^n grid the low 16-n bits of every word are zero and the
  slices below them provably leave the cells at zero, so they are not
  drawn.  Every slice that is drawn keeps its pinned counter, so the
  bits are those of the full 16-slice ladder;
* **pop-count accumulation** — two-pulse AND + SWAR pop-count, then a
  *signed integer* accumulation over the K grid axis in a VMEM scratch
  accumulator.

Layout.  The work unit is a ``(8, bn)`` slab of products for one output
row: eight contraction indices on the sublanes, ``bn`` output columns on
the lanes, so every Threefry round and Horner step is a full-width
vector op on native ``(8, 128)`` uint32 tiles.  ``x`` rides in
transposed (``(bk, bm)``: contraction on sublanes) so a row's eight
operands come out as a sublane column that broadcasts across the lanes;
per-row keys sit in SMEM (scalar prefetch).  Block shapes are aligned to
the TPU tiling here, whatever tile the caller asks for.

The bitstreams therefore never leave VMEM/registers — the in-situ-storage
property of the MRAM array mapped all the way down.  Integer accumulation
makes the result exactly associative, so the output is invariant to the
(block_m, block_n, block_k, lane_words) tiling: the autotuner may pick
any config without perturbing a single bit.  (Capacity notes: flat
product indices address 2^32 MULs per call and the signed per-output
accumulator holds |K·nbit| < 2^31.)

Two key modes, one kernel: per-call mode (one key, product index spans
the whole (M, K, N) grid) and per-row mode (one key per output row, row
term dropped from the product index) — the latter makes each row's bits
a function of its own key alone, which is what the continuous-batching
serve engine needs (`models/layers.py:_dense_rows`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels.sc_mul import LANE_BITS, NSLICES, popcount32
from repro.sc import ctr_rng, encoding

SUBLANES = 8     # contraction indices per slab (one vreg of sublanes)
LANES = 128      # lane width of a vreg


def encode_fx16(p, levels: int, quantize: bool):
    """|probability| tile -> fx16 bias words, THE host encoding in-kernel.

    Calls the selfsame ``sc/encoding.py`` helpers the packed path uses
    (both are pure jnp, hence kernel-safe), so in-kernel encoding equals
    the host encoding bit-for-bit by construction — one source of truth
    for the clamped grid round and the 16-bit ladder conversion.
    """
    if quantize:
        p = encoding.quantize_grid(p, levels)
    return encoding.to_fx16(p)


def _u32(v):
    """int32 -> uint32 by bit pattern (Mosaic has no unsigned converts)."""
    return jax.lax.bitcast_convert_type(v, jnp.uint32)


def _splat(scalar, shape):
    """int32 scalar -> uint32 vector of ``shape``."""
    return _u32(jnp.full(shape, scalar, jnp.int32))


def ladder_start(levels: int, quantize: bool) -> int:
    """First Horner slice that can set a cell, for a static operand grid.

    A quantized operand on a power-of-two grid of ``levels`` <= 2^16 is
    ``i / levels``, whose fx16 word ``i · 2^16 / levels`` has its low
    ``16 - log2(levels)`` bits zero (``encoding.to_fx16`` is exact
    there).  The ladder runs from the LSB with the cells at zero, and a
    zero mask bit keeps them at zero (``ux & 0``), so those slices add
    nothing.  Unquantized operands and other grids start at slice 0.
    """
    if quantize and 0 < levels <= encoding.FX16_ONE \
            and levels & (levels - 1) == 0:
        return NSLICES - (levels.bit_length() - 1)
    return 0


def word_counts(kx, ky, pid, px, pw, *, nwords: int, lane_words: int,
                start: int):
    """Pop-count of the cells surviving the two-pulse AND, over all words.

    kx / ky: (k0, k1) uint32 key pairs of the x and w operand streams;
    pid: uint32 product counters; px / pw: fx16 biases broadcast to
    ``pid``'s shape.  Word ``w`` of Horner slice ``s`` is
    ``threefry(key, (pid, s·nwords + w))[0]`` — the pinned layout of
    ``ctr_rng.product_counters``.  ``lane_words`` words are unrolled per
    loop step; integer sums are associative, so it never changes bits.
    The ladder runs slices ``start``..15 (``ladder_start``): the caller
    vouches that bits below ``start`` of every bias word are zero, and
    each slice drawn keeps its absolute counter, so the counts equal
    those of a ladder from slice 0.
    """
    shape = pid.shape

    def step(g, counts):
        for u in range(lane_words):
            w = g * lane_words + u
            tx = jnp.zeros(shape, jnp.uint32)
            ty = jnp.zeros(shape, jnp.uint32)
            for s in range(start, NSLICES):    # LSB -> MSB Horner ladder
                c1 = _splat(s * nwords + w, shape)
                ux = ctr_rng.threefry2x32(kx[0], kx[1], pid, c1)[0]
                uy = ctr_rng.threefry2x32(ky[0], ky[1], pid, c1)[0]
                mx = jnp.uint32(0) - ((px >> jnp.uint32(s)) & jnp.uint32(1))
                my = jnp.uint32(0) - ((pw >> jnp.uint32(s)) & jnp.uint32(1))
                tx = (mx & (ux | tx)) | (~mx & (ux & tx))
                ty = (my & (uy | ty)) | (~my & (uy & ty))
            survived = tx & ty                 # two-pulse AND (paper Fig. 5)
            counts = counts + jax.lax.bitcast_convert_type(
                popcount32(survived), jnp.int32)
        return counts

    return jax.lax.fori_loop(0, nwords // lane_words, step,
                             jnp.zeros(shape, jnp.int32))


def _sc_fused_kernel(keys_ref, xt_ref, w_ref, out_ref, acc_ref, *,
                     n_orig: int, row_stride: int, nbit: int, levels: int,
                     quantize: bool, nk: int, lane_words: int,
                     row_keys: bool):
    """One (bm, bn) output tile, one K step: draw, AND, pop-count, add.

    keys: SMEM (rows·4,) int32 raw threefry key words [kx0, kx1, ky0,
    ky1] per row (one row in per-call mode); xt: (bk, bm) / w: (bk, bn)
    signed probabilities in [-1, 1]; acc: (bm, bn) int32 signed
    pop-count accumulator (VMEM scratch).
    """
    i, j, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bk, bm = xt_ref.shape
    bn = w_ref.shape[1]
    shape = (SUBLANES, bn)
    nwords = nbit // LANE_BITS
    lane_m = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, bm), 1)
    # global (k, j) part of the pinned product counter, k offset 0
    gk = kk * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    gj = j * bn + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    kj = _u32(gk) * jnp.uint32(n_orig) + _u32(gj)

    def row(r, carry):
        gi = i * bm + r
        kbase = 4 * gi if row_keys else 0
        kx = (_splat(keys_ref[kbase], shape),
              _splat(keys_ref[kbase + 1], shape))
        ky = (_splat(keys_ref[kbase + 2], shape),
              _splat(keys_ref[kbase + 3], shape))
        base = _splat(gi, shape) * jnp.uint32(row_stride) + kj

        def kchunk(c, part):
            k0 = pl.multiple_of(c * SUBLANES, SUBLANES)
            # row r's eight operands as a sublane column (exact select)
            xs = xt_ref[pl.ds(k0, SUBLANES), :]
            xcol = jnp.sum(jnp.where(lane_m == r, xs, 0.0), axis=1,
                           keepdims=True)                   # (8, 1)
            ws = w_ref[pl.ds(k0, SUBLANES), :]              # (8, bn)
            px = jnp.broadcast_to(
                encode_fx16(jnp.abs(xcol), levels, quantize), shape)
            pw = encode_fx16(jnp.abs(ws), levels, quantize)
            sign = (jnp.sign(xcol).astype(jnp.int32)
                    * jnp.sign(ws).astype(jnp.int32))
            pid = base + _splat(k0, shape) * jnp.uint32(n_orig)
            counts = word_counts(kx, ky, pid, px, pw, nwords=nwords,
                                 lane_words=lane_words,
                                 start=ladder_start(levels, quantize))
            return part + sign * counts

        part = jax.lax.fori_loop(0, bk // SUBLANES, kchunk,
                                 jnp.zeros(shape, jnp.int32))
        acc_ref[pl.ds(r, 1), :] += jnp.sum(part, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, bm, row, 0)

    @pl.when(kk == nk - 1)
    def _emit():
        out_ref[...] = acc_ref[...]


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def aligned_blocks(m: int, k: int, n: int, block_m: int, block_n: int,
                   block_k: int) -> tuple:
    """The (bm, bn, bk) the kernel runs for a requested tile.

    TPU blocks need their last two dims divisible by (8, 128) or equal
    to the array's: ``bk`` rounds up to a sublane multiple; ``bn`` is
    the whole N or a lane multiple; ``bm`` (the lane dim of the
    transposed x block) is the whole M up to 128 rows, else a lane
    multiple.  Operands pad up to these blocks (padding draws nothing).
    """
    bk = _round_up(max(1, min(block_k, k)), SUBLANES)
    bn = _round_up(max(1, block_n), LANES)
    bn = n if bn >= n else bn
    bm = m if m <= LANES else _round_up(max(1, block_m), LANES)
    bm = m if bm >= m else bm
    return bm, bn, bk


@functools.partial(
    jax.jit,
    static_argnames=("k_orig", "n_orig", "nbit", "levels", "quantize",
                     "block_m", "block_n", "block_k", "lane_words",
                     "row_keys"))
def sc_fused_popcount(keys, x_signed_p, w_signed_p, *, k_orig: int,
                      n_orig: int, nbit: int, levels: int,
                      quantize: bool = True, block_m: int = 128,
                      block_n: int = 128, block_k: int = 1024,
                      lane_words: int = 1, row_keys: bool = False):
    """Fused SC matmul -> (M, N) int32 signed pop-count totals.

    keys: (M, 4) uint32 per-row raw key words [kx0, kx1, ky0, ky1] (the
    caller broadcasts one row in per-call mode); x: (M, K) / w: (K, N)
    signed probabilities.  ``k_orig`` / ``n_orig`` are the UNPADDED
    contraction/output widths — they define the flat product index, so
    padding never shifts a real product's stochastic draw.  With
    ``row_keys=True`` the row term drops out of the product index and
    every output row draws from its own key's stream.  The caller turns
    totals into the SC estimate via ``total / nbit · scale_x·scale_w``.
    """
    m, k = x_signed_p.shape
    k2, n = w_signed_p.shape
    assert k == k2 and keys.shape == (m, 4)
    assert nbit % LANE_BITS == 0, "fused engine packs 32 cells per word"
    assert k * nbit < 2 ** 31, \
        "signed int32 accumulator needs K*nbit < 2^31"
    bm, bn, bk = aligned_blocks(m, k, n, block_m, block_n, block_k)
    nwords = nbit // LANE_BITS
    lane = max(1, min(lane_words, nwords))
    while nwords % lane:
        lane -= 1
    xt = encoding.pad_to(encoding.pad_to(x_signed_p, bm, 0), bk, 1).T
    wp = encoding.pad_to(encoding.pad_to(w_signed_p, bk, 0), bn, 1)
    kp, mp = xt.shape
    np_ = wp.shape[1]
    key_rows = encoding.pad_to(keys, bm, 0) if row_keys else keys[:1]
    key_words = jax.lax.bitcast_convert_type(
        key_rows.astype(jnp.uint32), jnp.int32).reshape(-1)
    nk = kp // bk
    row_stride = 0 if row_keys else (k_orig * n_orig) & 0xFFFFFFFF
    kernel = functools.partial(
        _sc_fused_kernel, n_orig=n_orig, row_stride=row_stride, nbit=nbit,
        levels=levels, quantize=quantize, nk=nk, lane_words=lane,
        row_keys=row_keys)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(mp // bm, np_ // bn, nk),
            in_specs=[
                pl.BlockSpec((bk, bm), lambda i, j, kk, keys: (kk, i)),
                pl.BlockSpec((bk, bn), lambda i, j, kk, keys: (kk, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, keys: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=kernels.interpret_mode(),
        name="sc_fused_popcount",
    )(key_words, xt, wp)
    return out[:m, :n]
