"""Bit-exact simulator of the SOT-MRAM stochastic-computing MUL engine (§III-B).

The hardware sequence per MUL (paper Fig. 5):

    1. PRESET    — a long reverse pulse initializes every cell to "1".
    2. PULSE τ_X — each cell independently survives (stays "1") w.p.
                   P_usw(τ_X) = exp(-τ_X) at the operating current.
    3. PULSE τ_Y — surviving cells survive again w.p. P_usw(τ_Y).
    4. READ      — the fraction of "1"s estimates P_X · P_Y ∝ X·Y.

Each MRAM cell is an independent Bernoulli trial; two sequential pulses AND
two independent survival events, so the final per-bit distribution is
Bernoulli(P_X · P_Y) exactly. The simulator reproduces the *sequence*
(preset → pulse → pulse) bit-by-bit so that hardware-variance studies
(per-cell I_c spread, §IV-B) act on each pulse separately, exactly as the
paper's Monte-Carlo does.

Entropy: the TPU's in-kernel PRNG has no CPU interpreter path, so random
draws are counter-based threefry via ``jax.random`` — the statistical
contract (iid uniforms per cell per pulse) is identical to the
thermal randomness the device supplies.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import conversion, physics


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """One MRAM sub-array acting as an SC engine."""

    nbit: int = 1024                 # stochastic bits per MUL (2^n for n-bit operands)
    conv: conversion.ConversionConfig = conversion.ConversionConfig()
    # Cross-point row length limit (§III-D IR-drop discussion): a physical row
    # holds at most this many cells; nbit cells occupy ceil(nbit/row) rows that
    # are written simultaneously (multi-row activation).
    row_length: int = 256

    @property
    def rows_per_mul(self) -> int:
        return -(-self.nbit // self.row_length)


def preset(shape) -> jnp.ndarray:
    """Step 1: all cells to '1' (deterministic strong reverse pulse)."""
    return jnp.ones(shape, dtype=jnp.uint8)


def apply_pulse(key, state, tau_ns, *, i_ua=physics.I_C_UA, i_c_ua=physics.I_C_UA,
                delta=physics.DELTA):
    """One stochastic write pulse applied to every cell in ``state``.

    ``tau_ns`` broadcasts against ``state`` (scalar per-MUL pulse, or per-cell
    when modeling DTC/driver variance). ``i_c_ua`` may be a per-cell array for
    σ(I_c) studies. A cell at "1" survives w.p. P_usw; a cell already at "0"
    stays "0" (the pulse drives toward "0" only — paper Fig. 5 polarity).
    """
    p_survive = physics.p_unswitched(tau_ns, i_ua, delta=delta, i_c_ua=i_c_ua)
    u = jax.random.uniform(key, state.shape)
    survived = (u < p_survive).astype(state.dtype)
    return state * survived


def readout(state) -> jnp.ndarray:
    """Step 4: pop-count → probability estimate (fraction of remaining 1s)."""
    n = state.shape[-1]
    return jnp.sum(state, axis=-1, dtype=jnp.float32) / n


@partial(jax.jit, static_argnums=(3,))
def sc_multiply(key, x_int, y_int, cfg: EngineConfig):
    """Full §III MUL between two unsigned n-bit operands, bit-exact.

    Returns ``(p_est, product_int)`` where ``p_est ≈ P_X·P_Y`` and
    ``product_int`` is the decoded 2n-bit product estimate
    ``round(p_est · 2^{2n})``. Operands may be arrays (batched MULs — each MUL
    gets its own ``nbit`` cells, i.e. its own sub-array).
    """
    x_int = jnp.asarray(x_int, jnp.int32)
    y_int = jnp.asarray(y_int, jnp.int32)
    batch_shape = jnp.broadcast_shapes(x_int.shape, y_int.shape)
    cells = batch_shape + (cfg.nbit,)

    tau_x = conversion.operand_to_tau(x_int, cfg.conv)
    tau_y = conversion.operand_to_tau(y_int, cfg.conv)

    kx, ky = jax.random.split(key)
    state = preset(cells)
    state = apply_pulse(kx, state, tau_x[..., None])
    state = apply_pulse(ky, state, tau_y[..., None])

    p_est = readout(state)
    levels_sq = cfg.conv.levels * cfg.conv.levels
    product = jnp.round(p_est * levels_sq).astype(jnp.int32)
    return p_est, product


def _profile_cells(profile: physics.DeviceProfile, batch_shape, nbit: int):
    """Realized per-cell (delta, i_c) for a batch of MULs: MUL ``q`` of
    the batch occupies virtual cells ``q*nbit ..`` of the profile's
    frozen map, so batched engine runs and the variance studies read the
    SAME manufacturing spread the ``array`` backend does."""
    n_muls = 1
    for d in batch_shape:
        n_muls *= int(d)
    delta_c, ic_c = physics.mul_cell_params(profile, n_muls, nbit)
    shape = tuple(batch_shape) + (nbit,)
    return delta_c.reshape(shape), ic_c.reshape(shape)


@partial(jax.jit, static_argnums=(3,), static_argnames=("profile",))
def sc_multiply_states(key, tau_x, tau_y, cfg: EngineConfig,
                       *, i_c_ua=physics.I_C_UA, profile=None):
    """Lower-level entry: pulses already converted; returns the raw cell states.

    Used by the variance studies (per-cell ``i_c_ua`` arrays) and by tests
    that assert on the distribution of the bits themselves.

    ``profile`` (a :class:`physics.DeviceProfile`) is the one device knob:
    it supplies realized per-cell (Delta, I_c) from the profile's frozen
    variation maps and overrides a loose ``i_c_ua``.  Variation only —
    stuck-at / retention FAULTS are an array-readout phenomenon and are
    injected at the arch backend (``arch/backend.py``), not per-MUL here.
    """
    batch_shape = jnp.broadcast_shapes(jnp.shape(tau_x), jnp.shape(tau_y))
    cells = batch_shape + (cfg.nbit,)
    delta = physics.DELTA
    i_ua = physics.I_C_UA
    if profile is not None:
        delta, i_c_ua = _profile_cells(profile, batch_shape, cfg.nbit)
        i_ua = profile.i_c_ua       # operating current = nominal I_c
    kx, ky = jax.random.split(key)
    state = preset(cells)
    state = apply_pulse(kx, state, jnp.asarray(tau_x)[..., None],
                        i_ua=i_ua, i_c_ua=i_c_ua, delta=delta)
    state = apply_pulse(ky, state, jnp.asarray(tau_y)[..., None],
                        i_ua=i_ua, i_c_ua=i_c_ua, delta=delta)
    return state


def mac_rows(key, w_int, x_int, cfg: EngineConfig):
    """Paper §III-C vectored MAC: ``Σ_i w_i·x_i`` over a column of MULs.

    Performs each MUL in its own sub-array (rows of the same bank), then the
    two-step pop-count (row-wise CSA, column-wise FA) is modeled in
    popcount.py; here we return the raw per-MUL states stacked on axis 0 so
    the pop-count strategies can be applied and compared.
    """
    w_int = jnp.asarray(w_int, jnp.int32)
    x_int = jnp.asarray(x_int, jnp.int32)
    assert w_int.shape == x_int.shape
    _, states = jax.lax.scan(
        lambda carry, wx: (
            jax.random.fold_in(carry, 1),
            sc_multiply_states(carry, conversion.operand_to_tau(wx[0], cfg.conv),
                               conversion.operand_to_tau(wx[1], cfg.conv), cfg),
        ),
        key, (w_int, x_int))
    return states
