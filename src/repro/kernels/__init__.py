"""Pallas kernels of the SC substrate and the paged decode path.

Every ``pallas_call`` here takes its mode from :func:`interpret_mode`,
which follows the platform JAX computes on: the CPU runs the kernels in
the Pallas interpreter (the correctness harness the tests use), a TPU
compiles them through Mosaic.  There is no option to choose otherwise.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on ``cpu`` (Pallas interpreter), False on ``tpu`` (Mosaic).

    Any other platform raises: the kernels are written for the TPU and
    have no GPU lowering.
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run on tpu (compiled) or cpu (interpreted), not "
        f"on {platform!r}")
