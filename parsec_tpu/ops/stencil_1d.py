"""The 1D stencil mini-app (PaRSEC ``tests/apps/stencil/``:
``testing_stencil_1D.c`` over ``stencil_1D.jdf``) as a PTG task graph.

A is a tiled matrix; every ROW of it is an independent 1D problem along
the column index: ``u_t[r, j] = sum_{d=-R..R} w[d] * u_{t-1}[r, j+d]``,
``u`` zero outside ``0 <= j < N``, Jacobi (step t reads step t-1 only).
One task a tile a step: STENCIL(t, m, n) reads version t-1 of its own
tile and the R columns of tiles (m, n-1) and (m, n+1), version t-1,
that border it, and writes version t.  A neighbour gets the ghost
region and not the tile: each task writes its first and last R columns
as two WRITE-only flows beside X, in the same device call, and SNAP(m,
n) makes step 0's ghosts.  No task waits for a step to finish anywhere
but in its own three tiles, so the ready set is a wavefront over the
time steps.

No body reads a local: the tasks of every step stack, and a tile at
the matrix's edge (its L or R flow is NULL: the zero boundary) stacks
with the other tiles at that edge.
"""
from __future__ import annotations

from math import comb
from typing import Optional, Sequence, Tuple

import numpy as np

from ..collections.matrix import TiledMatrix
from ..dsl import ptg
from .blocking import run_blocking

STENCIL_1D_JDF = """
descA [ type="collection" ]
MT [ type="int" ]
NT [ type="int" ]
NI [ type="int" ]
RAD [ type="int" ]
W [ type="object" ]

SNAP(m, n)

m = 0 .. MT-1
n = 0 .. NT-1

: descA( m, n )

READ  X  <- descA( m, n )
         -> X STENCIL( 1, m, n )
WRITE GL -> (n > 0) ? R STENCIL( 1, m, n-1 )     [shape="(RAD, descA.tile_shape(m, n)[0])"]
WRITE GR -> (n < NT-1) ? L STENCIL( 1, m, n+1 )  [shape="(RAD, descA.tile_shape(m, n)[0])"]

; NI + 1

BODY [type=tpu]
{
    GL, GR = ops.stencil_ghosts(X, RAD)
}
END

STENCIL(t, m, n)

t = 1 .. NI
m = 0 .. MT-1
n = 0 .. NT-1

: descA( m, n )

RW    X  <- (t == 1) ? X SNAP( m, n ) : X STENCIL( t-1, m, n )
         -> (t < NI) ? X STENCIL( t+1, m, n ) : descA( m, n )
READ  L  <- (n > 0 and t == 1) ? GR SNAP( m, n-1 )
         <- (n > 0 and t > 1) ? GR STENCIL( t-1, m, n-1 )
READ  R  <- (n < NT-1 and t == 1) ? GL SNAP( m, n+1 )
         <- (n < NT-1 and t > 1) ? GL STENCIL( t-1, m, n+1 )
WRITE GL -> (n > 0 and t < NI) ? R STENCIL( t+1, m, n-1 )     [shape="(RAD, descA.tile_shape(m, n)[0])"]
WRITE GR -> (n < NT-1 and t < NI) ? L STENCIL( t+1, m, n+1 )  [shape="(RAD, descA.tile_shape(m, n)[0])"]

; NI + 1 - t

BODY [type=tpu]
{
    X = ops.stencil_tile(X, L, R, W)
    GL, GR = ops.stencil_ghosts(X, RAD)
}
END
"""

_factory = None


def stencil_1d_factory() -> "ptg.JDFFactory":
    global _factory
    if _factory is None:
        _factory = ptg.compile_jdf(STENCIL_1D_JDF, name="stencil_1d")
    return _factory


def stencil_weights(radius: int) -> Tuple[float, ...]:
    """The default weights of a radius: the binomial smoother
    C(2R, k) / 4^R, (0.25, 0.5, 0.25) at R = 1."""
    return tuple(comb(2 * radius, k) / 4.0 ** radius
                 for k in range(2 * radius + 1))


def stencil_1d_taskpool(A: TiledMatrix, iterations: int, radius: int = 1,
                        weights: Optional[Sequence[float]] = None):
    from .. import ops as ops_module
    if iterations < 1 or radius < 1:
        raise ValueError(f"stencil_1d: iterations and radius must be at "
                         f"least 1, got {iterations} and {radius}")
    weights = stencil_weights(radius) if weights is None \
        else tuple(float(w) for w in weights)
    if len(weights) != 2 * radius + 1:
        raise ValueError(f"stencil_1d: radius {radius} takes "
                         f"{2 * radius + 1} weights, got {len(weights)}")
    if A.ln % A.nb or A.nb < radius:
        raise ValueError(f"stencil_1d needs whole tiles of at least "
                         f"{radius} columns; got ln={A.ln} nb={A.nb}")
    assert np.dtype(A.dtype) == np.float32, "stencil_1d steps f32 storage"
    tp = stencil_1d_factory().new(descA=A, MT=A.mt, NT=A.nt,
                                  NI=int(iterations), RAD=int(radius),
                                  W=weights)
    tp.global_env["ops"] = ops_module
    return tp


def stencil_1d(context, A: TiledMatrix, iterations: int, radius: int = 1,
               weights: Optional[Sequence[float]] = None) -> None:
    """Take ``iterations`` Jacobi steps of the (2 ``radius`` + 1)-point
    stencil along every row of the f32 tiled matrix A, in place, zero
    outside the matrix.  On return A's tiles hold step ``iterations``
    as their newest copies.  Blocking: enqueue + wait."""
    run_blocking(context, "stencil_1d",
                 [stencil_1d_taskpool(A, iterations, radius, weights)])
