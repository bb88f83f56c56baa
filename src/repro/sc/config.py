"""Frozen configuration for the SC multiplication substrate.

One ``ScConfig`` fully determines how ``repro.sc.sc_dot`` computes a
matmul: which registered backend runs it, how many stochastic bits back
each scalar product, how operands quantize onto the paper's DTC grid, and
(for the Pallas backends) the kernel tile shape. The dataclass is frozen
and hashable so it can ride through ``jax.jit`` / ``custom_vjp`` as a
static argument.
"""

from __future__ import annotations

import contextlib
import dataclasses

from repro.core.physics import DeviceProfile


@dataclasses.dataclass(frozen=True)
class ScConfig:
    """Configuration of one SC matmul substrate (frozen, hashable).

    Attributes:
        backend: name of a backend in the ``repro.sc`` registry —
            one of ``exact | moment | bitexact | pallas_moment |
            pallas_bitexact | pallas_fused | array`` out of the box (see
            ``docs/backends.md`` for the trade-offs), or anything
            registered via :func:`repro.sc.register_backend`.
            ``pallas_fused`` ignores the ``block_*`` tiles below and
            takes its tiling from the autotune cache
            (``repro.sc.autotune``; bitwise identical either way).
        nbit: stochastic bits per scalar product — the number of MRAM
            cells each MUL occupies (paper: 2**operand_bits).  Error
            std scales as 1/sqrt(nbit).
        operand_bits: resolution of the LUT/DTC operand grid encoded
            probabilities snap to (paper §III-A: 10).
        quantize: apply that operand-grid quantization (disable for
            backend-numerics studies on un-quantized operands).
        block_m / block_n / block_k: Pallas moment-kernel tile shape
            (clamped per-call to the operand shape).

    Whether a Pallas kernel is interpreted or compiled is not
    configuration: it follows the platform
    (:func:`repro.kernels.interpret_mode`).
    """

    backend: str = "exact"      # name in the repro.sc registry
    nbit: int = 1024            # stochastic bits per scalar product
    operand_bits: int = 10      # quantization of encoded probabilities (paper: 10)
    quantize: bool = True       # apply the LUT/DTC-grid operand quantization
    # Pallas kernel tiling (moment kernel; clamped to the operand shape)
    block_m: int = 128
    block_n: int = 128
    block_k: int = 512
    # Device-realism profile (core/physics.py:DeviceProfile): frozen
    # per-cell variation + bit-error rates.  None or an ideal profile is
    # bit-identical to the paper's idealized math on every backend; a
    # non-ideal profile is realized by the ``array`` backend only (the
    # functional backends model the ideal device by construction).
    device: DeviceProfile | None = None

    def replace(self, **kw) -> "ScConfig":
        """Functional update, e.g. ``cfg.replace(backend="moment")``."""
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Ambient device profile: one knob for call sites that build their own
# ScConfig internally (models/layers.py:dense, and through it the serve
# engines).  ``build_engine(options=ServeOptions(fault_profile=...))``
# enters this scope around each tick so every stochastic matmul the model
# traces picks the profile up without threading it through ModelConfig.
# ---------------------------------------------------------------------------

_PROFILE_STACK: list[DeviceProfile] = []


@contextlib.contextmanager
def use_device_profile(profile: DeviceProfile | None):
    """Scope under which internally-constructed ``ScConfig``s carry
    ``device=profile``.  ``None`` is allowed and means no-op (callers can
    pass an unconditional context)."""
    if profile is None:
        yield
        return
    _PROFILE_STACK.append(profile)
    try:
        yield
    finally:
        _PROFILE_STACK.pop()


def current_device_profile() -> DeviceProfile | None:
    """Innermost :func:`use_device_profile` scope, or None."""
    return _PROFILE_STACK[-1] if _PROFILE_STACK else None
