"""General solves on whole arrays: ``dgetrs`` / ``dgesv`` over
``dgetrf``'s packed factors (DPLASMA zgetrs / zgesv), each ONE jitted
XLA program per shape, not a task DAG.  (The SPD inverse family --
``dtrtri``, ``dlauum``, ``dpotri``, ``dpoinv`` -- is tiled and runs
through the runtime: ops/dpoinv.py.)
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["dgetrs", "dgesv"]


@functools.lru_cache(maxsize=64)
def _jit_getrs(shape, dtype_name: str):
    import jax
    from jax import lax

    def f(LU, piv, B):
        Bp = B[piv]
        Y = lax.linalg.triangular_solve(LU, Bp, left_side=True, lower=True,
                                        unit_diagonal=True)
        return lax.linalg.triangular_solve(LU, Y, left_side=True,
                                           lower=False)
    return jax.jit(f)


def dgetrs(LU, piv, B):
    """Solve A X = B from dgetrf's packed factors + pivot vector."""
    if LU.shape[0] != LU.shape[1]:
        raise ValueError(
            f"dgetrs needs square packed factors, got {LU.shape} "
            f"(rectangular dgetrf output has no solve)")
    return _jit_getrs((LU.shape, B.shape), np.dtype(B.dtype).name)(
        LU, piv, B)


def dgesv(A, B, nb: int = 256):
    """General solve A X = B: pivoted LU + two triangular solves
    (ref: DPLASMA zgesv)."""
    from .dgetrf import dgetrf
    LU, piv = dgetrf(A, nb=nb)
    return dgetrs(LU, piv, B)
