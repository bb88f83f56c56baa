"""Pallas TPU kernel: fused moment-matched SC matmul (the beyond-paper MAC).

Computes, in ONE pass over the operand tiles (classic (i, j, k) matmul grid
with three f32 VMEM accumulators):

    mean  += sx_tile @ sw_tile          (signed probabilities — the MXU dot)
    sum_p += |sx|   @ |sw|              (Σ_k p_x·p_w)
    sum_p2+= sx²    @ sw²               (Σ_k p_x²·p_w², signs square away)

and at the final k-step emits

    out = (mean + noise · sqrt(max(sum_p − sum_p2, 0) / nbit)) · scale

which is the CLT-exact distribution of the SOT-MRAM MAC pop-count
(mean = exact product, variance = Σ_k p(1−p)/nbit — see the moment
backend in sc/backends.py for the derivation). All three dots ride the same operand tiles, so arithmetic
intensity is 3× a plain matmul at identical HBM traffic; the Gaussian noise
is a (bm, bn) input tile consumed once at the epilogue.

MXU alignment: block sizes default to 128×128×512 (f32); the K reduction is
the innermost ("arbitrary") grid axis so accumulators live across k-steps in
VMEM scratch — the standard Pallas TPU matmul pipeline shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _sc_mac_kernel(x_ref, w_ref, noise_ref, out_ref,
                   acc_mean, acc_p, acc_p2, *, inv_nbit: float, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_mean[...] = jnp.zeros_like(acc_mean)
        acc_p[...] = jnp.zeros_like(acc_p)
        acc_p2[...] = jnp.zeros_like(acc_p2)

    x = x_ref[...]          # (bm, bk) signed probabilities sx·px
    w = w_ref[...]          # (bk, bn) signed probabilities sw·pw
    acc_mean[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    acc_p[...] += jnp.dot(jnp.abs(x), jnp.abs(w),
                          preferred_element_type=jnp.float32)
    acc_p2[...] += jnp.dot(x * x, w * w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        var = jnp.maximum(acc_p[...] - acc_p2[...], 0.0) * inv_nbit
        out_ref[...] = acc_mean[...] + noise_ref[...] * jnp.sqrt(var)


def _box_muller(bits_a, bits_b):
    """Standard normals from two uint32 words (Box-Muller on the VPU)."""
    u1 = (bits_a >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    u2 = (bits_b >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    u1 = jnp.maximum(u1, 1e-12)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos(2.0 * jnp.pi * u2)


def _sc_mac_kernel_prng(seed_ref, x_ref, w_ref, out_ref,
                        acc_mean, acc_p, acc_p2, *, inv_nbit: float, nk: int):
    """In-kernel-PRNG variant (TPU only): the Gaussian epilogue noise is
    synthesized from ``pltpu.prng_random_bits`` instead of streaming an
    (M, N) noise tile from HBM — removing one of the four HBM operands
    (EXPERIMENTS §Perf cell-3 iteration 3). Seeded per output tile so every
    (i, j) block draws an independent stream."""
    from jax.experimental.pallas import tpu as pltpu

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_mean[...] = jnp.zeros_like(acc_mean)
        acc_p[...] = jnp.zeros_like(acc_p)
        acc_p2[...] = jnp.zeros_like(acc_p2)

    x = x_ref[...]
    w = w_ref[...]
    acc_mean[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    acc_p[...] += jnp.dot(jnp.abs(x), jnp.abs(w),
                          preferred_element_type=jnp.float32)
    acc_p2[...] += jnp.dot(x * x, w * w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        pltpu.prng_seed(seed_ref[0], pl.program_id(0), pl.program_id(1))
        shape = acc_mean.shape
        bits_a = pltpu.prng_random_bits(shape)
        bits_b = pltpu.prng_random_bits(shape)
        noise = _box_muller(bits_a.astype(jnp.uint32),
                            bits_b.astype(jnp.uint32))
        var = jnp.maximum(acc_p[...] - acc_p2[...], 0.0) * inv_nbit
        out_ref[...] = acc_mean[...] + noise * jnp.sqrt(var)


@functools.partial(
    jax.jit,
    static_argnames=("nbit", "block_m", "block_n", "block_k"))
def sc_mac_fused(x_signed_p, w_signed_p, noise, *, nbit: int = 1024,
                 block_m: int = 128, block_n: int = 128, block_k: int = 512):
    """Fused SC matmul on pre-encoded signed probabilities.

    x_signed_p: (M, K) f32 in [-1, 1]; w_signed_p: (K, N) f32 in [-1, 1];
    noise: (M, N) f32 standard normal. Caller multiplies the output by
    scale_x·scale_w (kept outside so the kernel stays scale-free).
    Shapes must be multiples of the block sizes (ops.py pads).
    """
    m, k = x_signed_p.shape
    k2, n = w_signed_p.shape
    assert k == k2 and noise.shape == (m, n)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    nk = k // bk
    grid = (m // bm, n // bn, nk)
    kernel = functools.partial(_sc_mac_kernel, inv_nbit=1.0 / nbit, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        # three f32 accumulators resident across the k loop
        scratch_shapes=[_vmem(bm, bn), _vmem(bm, bn), _vmem(bm, bn)],
        compiler_params=_TPU_PARAMS,
        interpret=kernels.interpret_mode(),
        name="sc_mac",
    )(x_signed_p, w_signed_p, noise)


@functools.partial(
    jax.jit,
    static_argnames=("nbit", "block_m", "block_n", "block_k"))
def sc_mac_fused_prng(seed, x_signed_p, w_signed_p, *, nbit: int = 1024,
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 512):
    """TPU-only variant: Gaussian noise generated ON-CHIP per output tile
    (``pltpu.prng_random_bits`` + Box-Muller), cutting HBM traffic from
    (MK + KN + 2MN) to (MK + KN + MN) floats. No CPU interpret path —
    ``pltpu.prng_*`` has no interpreter implementation on the CPU —
    so correctness is carried by the epilogue-math equivalence with
    ``sc_mac_fused`` (identical accumulators, tested) and the Box-Muller
    transform (unit-tested on CPU directly). seed: (1,) int32."""
    m, k = x_signed_p.shape
    k2, n = w_signed_p.shape
    assert k == k2
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    nk = k // bk
    grid = (m // bm, n // bn, nk)
    kernel = functools.partial(_sc_mac_kernel_prng, inv_nbit=1.0 / nbit,
                               nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[_vmem(bm, bn), _vmem(bm, bn), _vmem(bm, bn)],
        compiler_params=_TPU_PARAMS,
        interpret=False,
        name="sc_mac",
    )(seed, x_signed_p, w_signed_p)


def _vmem(bm, bn):
    return pltpu.VMEM((bm, bn), jnp.float32)


_TPU_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
