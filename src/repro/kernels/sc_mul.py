"""Pallas TPU kernel: packed bit-exact SC multiplication engine.

This kernel plays the role of one bank of cross-point SOT-MRAM sub-arrays
(paper Fig. 4/5): for a batch of M MULs it materializes the stochastic bit
arrays, applies the two-pulse AND semantics, and pop-counts — all inside one
VMEM-resident pass, so the "data explosion" of SC never touches HBM
(the paper's in-situ-storage property mapped to in-VMEM residency).

Bit representation: 32 stochastic cells per ``uint32`` lane word. Per-bit
Bernoulli(p) draws are synthesized from iid uniform words with the
**bit-sliced Horner ladder** (the classic weighted-bitstream construction):

    t = 0
    for slice j = LSB..MSB of p (16-bit fixed point):
        t = u_j | t   if bit_j(p) else   u_j & t

which yields P(bit of t = 1) = p exactly to 2^-16, for all 32 lanes of every
word in parallel — this is the TPU-native analogue of the row-parallel
stochastic write (every cell sees an independent coin with the same bias).

Pop-count is SWAR (shift-mask-add) on the packed words, fused with the
generation so the bits live and die inside VMEM.

Entropy source: random words are *inputs*, generated by the caller
(``pallas_bitexact`` feeds the pinned counter-based stream of
``sc/ctr_rng.py``, which the fused engine regenerates in-kernel), so the
packed and fused engines draw the same bits from the same key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels

NSLICES = 16        # fixed-point precision of the Bernoulli bias (2^-16)
LANE_BITS = 32      # stochastic cells per packed word


def bernoulli_words(p_fx16, u_slices):
    """Packed Bernoulli(p) words from NSLICES uniform words (Horner ladder).

    p_fx16:   (bm, 1)  uint32 — bias in 16-bit fixed point (p·2^16, clamped)
    u_slices: (bm, NSLICES, bw) uint32 — iid uniform random words
    returns:  (bm, bw) uint32 — each bit iid Bernoulli(p) per row
    """
    t = jnp.zeros(u_slices.shape[:1] + u_slices.shape[2:], jnp.uint32)
    for j in range(NSLICES):            # LSB -> MSB of the fixed-point bias
        bit = (p_fx16 >> j) & jnp.uint32(1)          # (bm, 1)
        mask = (jnp.uint32(0) - bit)                 # 0 or 0xFFFFFFFF
        u = u_slices[:, j, :]
        t = (mask & (u | t)) | (~mask & (u & t))
    return t


def popcount32(v):
    """SWAR pop-count of every uint32 word."""
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (v * jnp.uint32(0x01010101)) >> 24


def _sc_mul_kernel(px_ref, py_ref, ux_ref, uy_ref, out_ref):
    """One tile: bm MULs × bw packed words.

    px/py: (bm, 1) uint32 biases; ux/uy: (bm, NSLICES, bw) uniform words;
    out: (bm, 1) int32 pop-counts of the surviving cells.
    """
    px = px_ref[...]
    py = py_ref[...]
    bits_x = bernoulli_words(px, ux_ref[...])   # pulse τ_X survival draw
    bits_y = bernoulli_words(py, uy_ref[...])   # pulse τ_Y survival draw
    survived = bits_x & bits_y                  # two-pulse AND (Fig. 5)
    # per-word counts are <= 32, so the int32 view is exact (and Mosaic
    # reduces signed integers only)
    counts = jax.lax.bitcast_convert_type(popcount32(survived), jnp.int32)
    out_ref[...] = jnp.sum(counts, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_m",))
def sc_mul_popcount(p_x_fx16, p_y_fx16, rand_x, rand_y, *,
                    block_m: int = 8):
    """Batched bit-exact SC MUL: returns pop-counts, shape (M,) int32.

    p_*_fx16: (M,) uint32 biases (p·2^16); rand_*: (M, NSLICES, W) uint32.
    nbit = 32·W stochastic cells per MUL. M must be a multiple of block_m
    (:func:`sc_mul_bitexact` pads).
    """
    m, nslices, w = rand_x.shape
    assert nslices == NSLICES and m % block_m == 0
    grid = (m // block_m,)
    out = pl.pallas_call(
        _sc_mul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_m, NSLICES, w), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_m, NSLICES, w), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.int32),
        interpret=kernels.interpret_mode(),
        name="sc_mul",
    )(p_x_fx16.reshape(m, 1), p_y_fx16.reshape(m, 1), rand_x, rand_y)
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("nbit", "block_m"))
def sc_mul_bitexact(key, p_x, p_y, *, nbit: int = 1024, block_m: int = 8):
    """Batched bit-exact SC MUL of probability vectors via the Pallas engine.

    The direct way to exercise the packed engine on raw probabilities
    (quickstart / kernel tests); the model stack reaches it through the
    ``pallas_bitexact`` registry backend instead.  p_x, p_y: (M,) float
    probabilities.  Returns (M,) float estimates of p_x·p_y (pop-count /
    nbit).  nbit must be a multiple of 32.
    """
    # local import: repro.sc pulls this module in through the backend
    # registry, so a top-level import would be circular
    from repro.sc import encoding

    assert nbit % LANE_BITS == 0
    w = nbit // LANE_BITS
    m = p_x.shape[0]
    px = encoding.pad_to(encoding.to_fx16(p_x), block_m, 0)
    py = encoding.pad_to(encoding.to_fx16(p_y), block_m, 0)
    mp = px.shape[0]
    kx, ky = jax.random.split(key)
    shape = (mp, NSLICES, w)
    rx = jax.random.bits(kx, shape, jnp.uint32)
    ry = jax.random.bits(ky, shape, jnp.uint32)
    counts = sc_mul_popcount(px, py, rx, ry, block_m=block_m)
    return counts[:m].astype(jnp.float32) / nbit
