"""Mixed-precision tile Cholesky: ExaGeoStat's three-precision band
factorization on PaRSEC (Abdulah et al., IEEE TPDS 33(4), 2022), with
the conversions on the flows (Cao et al., IEEE Cluster 2023).

``dpotrf``'s dataflow, task for task, with the precision of a tile
product set by the distance d = m - n, in tiles, of the tile it writes
from the diagonal:

- d < ``band_high``: *hi*, f32 operands at the process's matmul
  precision (``highest`` as the benchmark runs it: six bf16 passes);
- ``band_high`` <= d < ``band_mid``: *mid*, f32 operands in three
  passes (``ops.gemm_nt_mid``, ``ops.trsm_panel_mid``);
- d >= ``band_mid``: *lo*, bf16 operands in one pass
  (``ops.gemm_nt_lo``).

Accumulation and every tile written are f32.  POTRF and SYRK write the
diagonal: hi.  GEMM(k, m, n) runs at the level of (m, n); TRSM(k, m) at
the level of (m, k), but never below mid (the source has no
half-precision TRSM).  The source's FP64 / FP32 / FP16 are hi / mid / lo
here: the v5e has no f64 unit and these are its three MXU precisions.

A level is a task CLASS (GEMM, GEMM_MID, GEMM_LO; TRSM, TRSM_MID): no
body reads ``k``, ``m`` or ``n``, so the tasks of a level stack and the
device trace names each level's programs.  A lo GEMM takes its A and B
as bf16 through ``[type=LO]`` on its in-deps: the reshape engine
(data/reshape.py) converts a TRSM's f32 output ONCE, on the chip, where
it is produced, every lo reader shares the copy, and it is dropped with
its last reader.  Mid and hi readers take the f32 tile itself.
"""
from __future__ import annotations

import numpy as np

from ..collections.matrix import TiledMatrix
from ..data.datatype import Datatype
from ..dsl import ptg
from .blocking import run_blocking

DPOTRF_MP_L_JDF = """
descA [ type="collection" ]
NT [ type="int" ]
BH [ type="int" ]
BM [ type="int" ]
LO [ type="object" ]

POTRF(k)

k = 0 .. NT-1

: descA( k, k )

RW T <- (k == 0) ? descA( k, k ) : T SYRK( k-1, k )
     -> T TRSM( k, k+1 .. min(NT-1, k+BH-1) )
     -> T TRSM_MID( k, k+BH .. NT-1 )
     -> descA( k, k )

; (NT - k) * 1000

BODY [type=tpu]
{
    T = ops.potrf(T)
}
END

TRSM(k, m)

k = 0 .. NT-2
m = k+1 .. min(NT-1, k+BH-1)

: descA( m, k )

READ T <- T POTRF( k )
RW   C <- (k == 0) ? descA( m, k ) : C GEMM( k-1, m, k )
       -> A SYRK( k, m )
       -> A GEMM( k, m, k+1 .. m-1 )
       -> B GEMM( k, m+1 .. min(NT-1, m+BH-1), m )
       -> B GEMM_MID( k, m+BH .. min(NT-1, m+BM-1), m )
       -> B GEMM_LO( k, m+BM .. NT-1, m )
       -> descA( m, k )

; (NT - m) * 100 + (NT - k) * 10

BODY [type=tpu]
{
    C = ops.trsm_panel(T, C)
}
END

TRSM_MID(k, m)

k = 0 .. NT-2
m = k+BH .. NT-1

: descA( m, k )

READ T <- T POTRF( k )
RW   C <- (k == 0) ? descA( m, k )
       <- (k > 0 && m - k < BM) ? C GEMM_MID( k-1, m, k )
       <- (k > 0 && m - k >= BM) ? C GEMM_LO( k-1, m, k )
       -> A SYRK( k, m )
       -> A GEMM( k, m, max(k+1, m-BH+1) .. m-1 )
       -> A GEMM_MID( k, m, max(k+1, m-BM+1) .. m-BH )
       -> A GEMM_LO( k, m, k+1 .. m-BM )
       -> B GEMM( k, m+1 .. min(NT-1, m+BH-1), m )
       -> B GEMM_MID( k, m+BH .. min(NT-1, m+BM-1), m )
       -> B GEMM_LO( k, m+BM .. NT-1, m )
       -> descA( m, k )

; (NT - m) * 100 + (NT - k) * 10

BODY [type=tpu]
{
    C = ops.trsm_panel_mid(T, C)
}
END

SYRK(k, m)

k = 0 .. NT-2
m = k+1 .. NT-1

: descA( m, m )

READ A <- (m - k < BH) ? C TRSM( k, m ) : C TRSM_MID( k, m )
RW   T <- (k == 0) ? descA( m, m ) : T SYRK( k-1, m )
       -> (m == k+1) ? T POTRF( m ) : T SYRK( k+1, m )

; (NT - m) * 1000

BODY [type=tpu]
{
    T = ops.syrk_ln(T, A)
}
END

GEMM(k, m, n)

k = 0 .. NT-3
m = k+2 .. NT-1
n = max(k+1, m-BH+1) .. m-1

: descA( m, n )

READ A <- (m - k < BH) ? C TRSM( k, m ) : C TRSM_MID( k, m )
READ B <- (n - k < BH) ? C TRSM( k, n ) : C TRSM_MID( k, n )
RW   C <- (k == 0) ? descA( m, n ) : C GEMM( k-1, m, n )
       -> (n == k+1) ? C TRSM( n, m ) : C GEMM( k+1, m, n )

; (NT - m) * 10

BODY [type=tpu]
{
    C = ops.gemm_nt(C, A, B)
}
END

GEMM_MID(k, m, n)

k = 0 .. NT-3
m = k+2 .. NT-1
n = max(k+1, m-BM+1) .. m-BH

: descA( m, n )

READ A <- C TRSM_MID( k, m )
READ B <- (n - k < BH) ? C TRSM( k, n ) : C TRSM_MID( k, n )
RW   C <- (k == 0) ? descA( m, n ) : C GEMM_MID( k-1, m, n )
       -> (n == k+1) ? C TRSM_MID( n, m ) : C GEMM_MID( k+1, m, n )

; (NT - m) * 10

BODY [type=tpu]
{
    C = ops.gemm_nt_mid(C, A, B)
}
END

GEMM_LO(k, m, n)

k = 0 .. NT-3
m = k+2 .. NT-1
n = k+1 .. m-BM

: descA( m, n )

READ A <- C TRSM_MID( k, m )                                       [type=LO]
READ B <- (n - k < BH) ? C TRSM( k, n ) : C TRSM_MID( k, n )       [type=LO]
RW   C <- (k == 0) ? descA( m, n ) : C GEMM_LO( k-1, m, n )
       -> (n == k+1) ? C TRSM_MID( n, m ) : C GEMM_LO( k+1, m, n )

; (NT - m) * 10

BODY [type=tpu]
{
    C = ops.gemm_nt_lo(C, A, B)
}
END
"""

_factory = None


def dpotrf_mp_factory() -> "ptg.JDFFactory":
    global _factory
    if _factory is None:
        _factory = ptg.compile_jdf(DPOTRF_MP_L_JDF, name="dpotrf_mp_L")
    return _factory


def dpotrf_mp(context, A: TiledMatrix, band_high: int = 2,
              band_mid: int = 5) -> None:
    """Factor the SPD f32 tiled matrix A in place (its lower triangle
    holds L on return, f32) with the tile products at three levels by
    the band of the tile written.  Blocking: enqueue + wait."""
    run_blocking(context, "dpotrf_mp",
                 [dpotrf_mp_taskpool(A, band_high, band_mid)])


def dpotrf_mp_taskpool(A: TiledMatrix, band_high: int = 2,
                       band_mid: int = 5):
    import jax.numpy as jnp

    from .. import ops as ops_module
    assert A.mt == A.nt and A.mb == A.nb and A.lm % A.mb == 0, \
        "dpotrf_mp needs a square grid of whole square tiles"
    assert np.dtype(A.dtype) == np.float32, "dpotrf_mp factors f32 storage"
    assert 1 <= band_high <= band_mid, (band_high, band_mid)
    tp = dpotrf_mp_factory().new(
        descA=A, NT=A.nt, BH=band_high, BM=band_mid,
        LO=Datatype(jnp.bfloat16, (A.mb, A.nb)))
    tp.global_env["ops"] = ops_module
    return tp


def converted_tiles(nt: int, band_mid: int) -> int:
    """TRSM outputs L(m, k) that some lo GEMM reads, each converted once:
    as A of GEMM_LO(k, m, n) for an n with m - n >= band_mid (m - k >
    band_mid), or as B of GEMM_LO(k, m', m) for an m' >= m + band_mid
    (m <= nt - 1 - band_mid)."""
    return sum(1 for k in range(nt - 1) for m in range(k + 1, nt)
               if m - k > band_mid or m <= nt - 1 - band_mid)
