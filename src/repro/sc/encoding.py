"""Canonical operand encoding shared by every SC backend.

This is the ONE place float tensors become stochastic-computing operands:

* sign/magnitude split — the paper's engine multiplies unsigned
  probabilities; signs are carried beside the magnitudes and multiply
  through the accumulation (standard SC practice).
* per-tensor max-abs scale — magnitudes map onto [0, 1] so every value is
  a valid Bernoulli bias; the product of the two scales is re-applied to
  the decoded output.
* operand-grid quantization — the paper drives pulse durations from an
  n-bit LUT/DTC (§III-A), so encoded probabilities snap to a 2^n grid.
* fx16 bias words — the packed Pallas engine consumes biases as 16-bit
  fixed point (the Horner-ladder resolution in kernels/sc_mul.py).

The deleted PR-1 shims (``core/scmac.py``, ``kernels/ops.py``) used to
each carry a copy of this logic; this module is the single home now.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


FX16_ONE = 1 << 16      # fixed-point unit of the packed-engine bias words


def encode(v, cfg):
    """float tensor -> (sign, probability, scale). p ∈ [0,1), v ≈ sign·p·scale.

    ``cfg`` needs ``quantize`` and ``operand_bits`` (any ScConfig-shaped
    object qualifies).

    The operand grid is the paper's n-bit LUT index space (§III-A): an
    operand X ∈ [0, 2^n - 1] encodes probability X / 2^n, so the top
    representable level is (2^n - 1)/2^n — index 2^n does not exist in the
    table.  Probabilities therefore snap to ``round(p·2^n)`` *clamped* to
    2^n - 1; the previous un-clamped round produced 2^n + 1 levels with
    p = 1.0 landing on the nonexistent index 2^n.
    """
    scale = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30)
    p = jnp.abs(v) / scale
    if cfg.quantize:
        p = quantize_grid(p, 1 << cfg.operand_bits)
    return jnp.sign(v), p, scale


def quantize_grid(p, levels: int):
    """Snap probabilities onto the paper's n-bit LUT/DTC operand grid.

    The clamped round described in :func:`encode` — THE single source of
    the grid formula: the host encoding above and the fused Pallas
    kernel's in-kernel encoding (``kernels/sc_fused.py``) both call this,
    which is what keeps their fx16 bias words bit-identical.
    """
    return jnp.clip(jnp.round(p * levels), 0, levels - 1) / levels


def decode(sign, p, scale):
    """Inverse of :func:`encode` (up to quantization)."""
    return sign * p * scale


def to_fx16(p):
    """Probability in [0, 1] -> 16-bit bias word w, Bernoulli bias w / 2^16.

    Round-to-nearest, so the round-trip through :func:`from_fx16` is EXACT
    on every operand grid of ``operand_bits <= 16``: a grid level
    p = i / 2^n maps to w = i·2^(16-n) and back losslessly.  p = 1.0 itself
    has no 16-bit word (w = 2^16 needs a 17th bit) and clamps to 65535;
    :func:`encode`'s clamped grid keeps quantized probabilities at
    (2^n - 1)/2^n or below, so the packed Pallas path never hits the clamp
    and max-magnitude operands are no longer biased downward.  The word
    converts through int32 (same value, < 2^16), a cast the fused
    kernel's TPU lowering supports where float -> uint32 is not.
    """
    w = jnp.clip(jnp.round(p * FX16_ONE), 0, FX16_ONE - 1).astype(jnp.int32)
    return jax.lax.bitcast_convert_type(w, jnp.uint32)


def from_fx16(w):
    """Bias word -> the probability the packed engine realizes (w / 2^16).

    This is exactly the per-bit probability of the Horner-ladder Bernoulli
    synthesis in ``kernels/sc_mul.py``, so ``from_fx16(to_fx16(p))`` is the
    bias the hardware path actually draws with.
    """
    return w.astype(jnp.float32) / FX16_ONE


def pad_to(x, multiple, axis):
    """Zero-pad ``axis`` of ``x`` up to the next multiple of ``multiple``."""
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)
