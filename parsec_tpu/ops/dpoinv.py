"""The inverse of an SPD matrix (dpoinv) as three composed PTG graphs.

DPLASMA's ``dplasma_dpoinv(parsec, dplasmaLower, A)`` (``tests/
testing_dpoinv.c``): the lower triangle of an SPD matrix is overwritten
by the lower triangle of its inverse, as ``dpotrf`` then ``dpotri``, and
``dplasma_dpotri_New`` is ``parsec_compose(dtrtri, dlauum)``.  Here the
three parts are three taskpools over ONE collection, chained by
:func:`~parsec_tpu.runtime.compound.compose` into one ``add_taskpool``:

- ``dpotrf_L`` (ops/dpotrf.py, as it is): ``A = L L'``;
- ``dtrtri_L``: ``L <- L^-1`` in place; PLASMA's ``pdtrtri`` loops, for
  ``k = 0 .. NT-1``: ``TRSMR(k, m)``, ``m > k``: ``A(m,k) <- -A(m,k)
  A(k,k)^-1``; ``GEMMI(k, m, n)``, ``m > k > n``: ``A(m,n) += A(m,k)
  A(k,n)``; ``TRSML(k, n)``, ``n < k``: ``A(k,n) <- A(k,k)^-1 A(k,n)``;
  ``TRTRI(k)``: ``A(k,k) <- A(k,k)^-1``;
- ``dlauum_L``: ``L <- L' L`` (lower) in place; PLASMA's ``pdlauum``
  loops, for ``k = 0 .. NT-1``: ``SYRKT(k, n)``, ``n < k``: ``A(n,n) +=
  A(k,n)' A(k,n)``; ``GEMMT(k, m, n)``, ``n < m < k``: ``A(m,n) +=
  A(k,m)' A(k,n)``; ``TRMM(k, n)``, ``n < k``: ``A(k,n) <- A(k,k)'
  A(k,n)``; ``LAUUM(k)``: ``A(k,k) <- A(k,k)' A(k,k)``.

Each part has ``NT (NT+1) (NT+2) / 6`` tasks (``NT``, ``NT (NT-1) / 2``
twice, ``NT (NT-1) (NT-2) / 6``), every kernel runs at the one static
shape ``NB x NB`` and no body reads a task local.

Writes after reads.  Both algorithms work in place: a step READS a tile
and then overwrites it (``A(k,n)`` by every ``GEMMI(k, *, n)`` and then
``TRSML(k, n)``; ``A(m,k)`` by every ``GEMMI(k, m, *)`` and then the
tile's next update; ``A(k,k)`` by the step's solves and then
``TRTRI(k)``; in ``dlauum`` every tile of block row ``k`` by the step's
products and then ``TRMM(k, n)`` / ``LAUUM(k)``).  A flow here does NOT
carry the value its producer made: a task's inputs are resolved when it
is staged in, from the tile's one copy on the device, which a writer's
epilog replaces.  So the JDFs state each anti-dependence as a CTL
gather from the tile's readers to its next writer, as a JDF over
in-place BLAS has to upstream; ``tests/test_dpoinv.py`` holds the
dispatch order to it and shows the result going wrong without them.
"""
from __future__ import annotations

from ..collections.matrix import TiledMatrix
from ..dsl import ptg
from ..runtime.compound import compose
from .blocking import run_blocking
from .dpotrf import dpotrf_taskpool

DTRTRI_L_JDF = """
descA [ type="collection" ]
NT [ type="int" ]

TRSMR(k, m)

k = 0 .. NT-2
m = k+1 .. NT-1

: descA( m, k )

READ T <- descA( k, k )
RW   C <- descA( m, k )
       -> A GEMMI( k, m, 0 .. k-1 )
       -> (m == k+1) ? B GEMMI( m, m+1 .. NT-1, k )
       -> (m == k+1) ? C TRSML( m, k ) : C GEMMI( k+1, m, k )
CTL  R -> R TRTRI( k )

; (NT - k) * 1000 + 900

BODY [type=tpu]
{
    C = ops.trsm_lower_right_neg(T, C)
}
END

GEMMI(k, m, n)

k = 1 .. NT-2
m = k+1 .. NT-1
n = 0 .. k-1

: descA( m, n )

READ A <- C TRSMR( k, m )
READ B <- (k == n+1) ? C TRSMR( n, k ) : C GEMMI( k-1, k, n )
RW   C <- (k == n+1) ? C TRSMR( n, m ) : C GEMMI( k-1, m, n )
       -> (m == k+1) ? B GEMMI( m, m+1 .. NT-1, n )
       -> (m == k+1) ? C TRSML( m, n ) : C GEMMI( k+1, m, n )
CTL  X -> X TRSML( k, n )
CTL  Y <- (n == k-1 && k >= 2) ? Y GEMMI( k-1, m, 0 .. k-2 )
       -> (m == k+1) ? Y TRSML( m, k ) : Y GEMMI( k+1, m, k )

; (NT - k) * 1000 + (NT - m) * 10

BODY [type=tpu]
{
    C = ops.gemm_nn(C, A, B)
}
END

TRSML(k, n)

k = 1 .. NT-1
n = 0 .. k-1

: descA( k, n )

READ T <- descA( k, k )
RW   C <- (k == n+1) ? C TRSMR( n, k ) : C GEMMI( k-1, k, n )
       -> descA( k, n )
CTL  X <- (k < NT-1) ? X GEMMI( k, k+1 .. NT-1, n )
CTL  Y <- (n == k-1 && k >= 2) ? Y GEMMI( k-1, k, 0 .. k-2 )
CTL  Z -> Z TRTRI( k )

; (NT - k) * 10

BODY [type=tpu]
{
    C = ops.trsm_lower(T, C)
}
END

TRTRI(k)

k = 0 .. NT-1

: descA( k, k )

RW   T <- descA( k, k )
       -> descA( k, k )
CTL  R <- (k < NT-1) ? R TRSMR( k, k+1 .. NT-1 )
CTL  Z <- (k > 0) ? Z TRSML( k, 0 .. k-1 )

; 0

BODY [type=tpu]
{
    T = ops.trtri_lower(T)
}
END
"""

DLAUUM_L_JDF = """
descA [ type="collection" ]
NT [ type="int" ]

LAUUM(k)

k = 0 .. NT-1

: descA( k, k )

RW   T <- descA( k, k )
       -> (k < NT-1) ? T SYRKT( k+1, k ) : descA( k, k )
CTL  X <- (k > 0) ? X TRMM( k, 0 .. k-1 )

; (NT - k) * 1000 + 900

BODY [type=tpu]
{
    T = ops.lauum_lower(T)
}
END

TRMM(k, n)

k = 1 .. NT-1
n = 0 .. k-1

: descA( k, n )

READ T <- descA( k, k )
RW   C <- descA( k, n )
       -> (k < NT-1) ? C GEMMT( k+1, k, n ) : descA( k, n )
CTL  X -> X LAUUM( k )
CTL  S <- S SYRKT( k, n )
CTL  G <- (n < k-1) ? G GEMMT( k, n+1 .. k-1, n )
CTL  H <- (n > 0) ? H GEMMT( k, n, 0 .. n-1 )

; (NT - k) * 1000 + 800

BODY [type=tpu]
{
    C = ops.trmm_lower_trans(T, C)
}
END

SYRKT(k, n)

k = 1 .. NT-1
n = 0 .. k-1

: descA( n, n )

READ A <- descA( k, n )
RW   T <- (k == n+1) ? T LAUUM( n ) : T SYRKT( k-1, n )
       -> (k < NT-1) ? T SYRKT( k+1, n ) : descA( n, n )
CTL  S -> S TRMM( k, n )

; (NT - k) * 1000 + 500

BODY [type=tpu]
{
    T = ops.syrk_lt(T, A)
}
END

GEMMT(k, m, n)

k = 2 .. NT-1
m = 1 .. k-1
n = 0 .. m-1

: descA( m, n )

READ A <- descA( k, m )
READ B <- descA( k, n )
RW   C <- (k == m+1) ? C TRMM( m, n ) : C GEMMT( k-1, m, n )
       -> (k < NT-1) ? C GEMMT( k+1, m, n ) : descA( m, n )
CTL  G -> G TRMM( k, n )
CTL  H -> H TRMM( k, m )

; (NT - k) * 1000 + (NT - m) * 10

BODY [type=tpu]
{
    C = ops.gemm_tn(C, A, B)
}
END
"""

_factories: dict = {}


def _factory(name: str, jdf: str) -> "ptg.JDFFactory":
    if name not in _factories:
        _factories[name] = ptg.compile_jdf(jdf, name=name)
    return _factories[name]


def _taskpool(name: str, jdf: str, A: TiledMatrix):
    from .. import ops as ops_module
    if A.mt != A.nt or A.mb != A.nb:
        raise ValueError(f"{name} needs a square grid of square tiles; got "
                         f"{A.mt}x{A.nt} tiles of {A.mb}x{A.nb}")
    tp = _factory(name, jdf).new(descA=A, NT=A.nt)
    tp.global_env["ops"] = ops_module
    return tp


def dtrtri_taskpool(A: TiledMatrix):
    """``L <- L^-1`` in place on the lower tiles of ``A``."""
    return _taskpool("dtrtri_L", DTRTRI_L_JDF, A)


def dlauum_taskpool(A: TiledMatrix):
    """``L <- L' L`` (lower) in place on the lower tiles of ``A``."""
    return _taskpool("dlauum_L", DLAUUM_L_JDF, A)


def dtrtri(context, A: TiledMatrix) -> None:
    """Invert the lower triangular tiled matrix ``A`` in place
    (DPLASMA's ``dplasma_dtrtri``, lower, non-unit).  Blocking."""
    run_blocking(context, "dtrtri", [dtrtri_taskpool(A)])


def dlauum(context, A: TiledMatrix) -> None:
    """``L' L`` of the lower triangular tiled matrix ``A`` in place,
    lower tiles (DPLASMA's ``dplasma_dlauum``).  Blocking."""
    run_blocking(context, "dlauum", [dlauum_taskpool(A)])


def dpotri(context, A: TiledMatrix) -> None:
    """The inverse of an SPD matrix from its Cholesky factor, in place
    (DPLASMA's ``dplasma_dpotri`` = ``parsec_compose(dtrtri, dlauum)``):
    ``A`` holds dpotrf's ``L`` in its lower tiles and, on return, the
    lower triangle of ``(L L')^-1``.  ONE ``add_taskpool``.  Blocking."""
    run_blocking(context, "dpotri",
                 [compose(dtrtri_taskpool(A), dlauum_taskpool(A))])


def dpoinv(context, A: TiledMatrix) -> None:
    """The inverse of the SPD tiled matrix ``A`` in place (DPLASMA's
    ``dplasma_dpoinv``, lower): on return the lower tiles hold the lower
    triangle of ``A^-1`` (a diagonal tile the whole symmetric block),
    the tiles above the diagonal are untouched.  Three taskpools over
    the one collection in ONE ``add_taskpool``: no host wait between
    the parts, and a part finds its tiles where the part before left
    them, on the device.  Blocking: enqueue + wait."""
    run_blocking(context, "dpoinv", [compose(
        compose(dpotrf_taskpool(A), dtrtri_taskpool(A)),
        dlauum_taskpool(A))])
