"""Pallas kernel: grouped matmul over expert-sorted rows (``moe_gmm``).

Dropless MoE dispatch (``models/moe.py``) sorts the (token, expert)
pairs of a whole step by expert, so expert ``e`` owns one contiguous run
of ``group_sizes[e]`` rows.  One ``pallas_call`` then multiplies every
run by its own expert's weight slab: ``out[r] = lhs[r] @ rhs[e(r)]``.

Adapted from ``jax.experimental.pallas.ops.tpu.megablox.gmm`` (its
group metadata is reused as is): the grid is (n tiles, active row tiles,
k tiles), and a row tile that two experts share is visited once per
expert, each visit storing only its own expert's rows.  Expert weights
stream from HBM once per row tile they own, so a decode step with a few
rows per expert reads each expert's slab about once.  Empty experts are
never visited.  Unlike megablox the call is named ``moe_gmm`` in the
compiled program and the profiler's trace, the contraction is never
ragged (the tile divides ``k``), and it carries a VJP for training
(``lhs`` through the same kernel, ``rhs`` through a plain per-expert
reduction).

The kernel runs compiled on a TPU and in the Pallas interpreter on the
CPU (:func:`repro.kernels.interpret_mode`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from repro import kernels

TM = 128                # rows per tile
TN = 256                # output columns per tile
TK = 2048               # contraction per tile


def _tile(dim: int, pref: int) -> int:
    """The largest multiple of 128 up to ``pref`` dividing ``dim``, or
    ``dim`` itself when it is small or has no such divisor."""
    for t in range(min(pref, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def _kernel(meta_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *, tm, tn,
            tiles_k):
    offsets, group_ids, m_tile_ids = meta_ref
    grid_id, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _():
        g = group_ids[grid_id]
        row = m_tile_ids[grid_id] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def _gmm(lhs, rhs, group_sizes):
    m, k = lhs.shape
    e, _, n = rhs.shape
    tm = min(TM, -(-m // 8) * 8)
    m_pad = -(-m // tm) * tm
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    tk, tn = _tile(k, TK), _tile(n, TN)
    meta, active = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m_pad, tm=tm,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=e,
        visit_empty_groups=False)

    def lhs_map(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], k_i

    def rhs_map(n_i, grid_id, k_i, meta):
        return meta[1][grid_id], k_i, n_i

    def out_map(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], n_i

    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, tiles_k=k // tk),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, active, k // tk),
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=kernels.interpret_mode(),
        name="moe_gmm",
    )(meta, lhs, rhs.astype(lhs.dtype))
    return out[:m]


@jax.custom_vjp
def moe_gmm(lhs, rhs, group_sizes):
    """Grouped matmul: ``lhs`` (m, k) rows sorted by group, ``rhs`` (e,
    k, n) one slab per group, ``group_sizes`` (e,) int rows per group
    summing to m.  Returns (m, n) in ``lhs.dtype``, accumulated in
    float32."""
    return _gmm(lhs, rhs, group_sizes)


def _fwd(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(res, g):
    lhs, rhs, group_sizes = res
    dlhs = _gmm(g.astype(lhs.dtype), jnp.swapaxes(rhs, 1, 2), group_sizes)
    gid = jnp.repeat(jnp.arange(rhs.shape[0]), group_sizes,
                     total_repeat_length=lhs.shape[0])

    def one(e):
        mine = (gid == e)[:, None]
        return jnp.dot(jnp.where(mine, lhs, 0).T.astype(jnp.float32),
                       g.astype(jnp.float32))

    drhs = jax.lax.map(one, jnp.arange(rhs.shape[0])).astype(rhs.dtype)
    return dlhs, drhs, None


moe_gmm.defvjp(_fwd, _bwd)
