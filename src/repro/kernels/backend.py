"""Where a Pallas kernel runs: compiled for the TPU, or interpreted on the CPU.

The one place the three kernel ops decide it.  The CPU backend runs the
kernel body through the Pallas interpreter (tests, CPU rehearsals); the TPU
compiles it through Mosaic.  Any other backend has neither, so it raises
instead of silently interpreting.
"""
from __future__ import annotations

import jax


def interpret() -> bool:
    """True on the CPU backend, False on the TPU; raises elsewhere."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas TPU kernels have no path on backend {platform!r} "
        "(only 'tpu', or 'cpu' in interpret mode)"
    )
