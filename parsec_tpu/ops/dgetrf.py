"""Tile LU factorization without pivoting (dgetrf_nopiv) as a PTG graph.

The right-looking tile LU with the DPLASMA task classes GETRF / TRSM_L
(row panel, yields U(k,n)) / TRSM_U (column panel, yields L(m,k)) / GEMM
(trailing update) — the dataflow of DPLASMA's zgetrf_nopiv.jdf on the
reference runtime (SURVEY.md §2.6, §7.2-10). No pivoting: intended for
diagonally-dominant or otherwise LU-stable matrices, as in the reference's
nopiv variant.

The diagonal-tile kernel is a fully static-shape masked update loop
(ops.getrf_nopiv) so XLA compiles one executable per tile shape; panel and
trailing updates are triangular solves and one GEMM per tile — all
MXU-shaped.

On return descA holds unit-lower L strictly below the diagonal and U on
and above: A = L U (verify by reconstruction).

LU WITH partial pivoting through the runtime is ``ops.dgetrf_1d``
(ops/dgetrf_1d.py: block columns, a pivot flow); ``dgetrf`` below is the
same factorization of a numpy array as one jitted program.
"""
from __future__ import annotations

import functools

import numpy as np

from ..collections.matrix import TiledMatrix
from ..dsl import ptg
from .blocking import run_blocking

DGETRF_JDF = """
descA [ type="collection" ]
MT [ type="int" ]
NT [ type="int" ]
KT [ type="int" ]

GETRF(k)

k = 0 .. KT-1

: descA( k, k )

RW A <- (k == 0) ? descA( k, k ) : C GEMM( k-1, k, k )
     -> descA( k, k )
     -> T TRSM_L( k, k+1 .. NT-1 )
     -> T TRSM_U( k, k+1 .. MT-1 )

; (KT - k) * 1000

BODY [type=tpu]
{
    A = ops.getrf_nopiv(A)
}
END

TRSM_L(k, n)

k = 0 .. KT-1
n = k+1 .. NT-1

: descA( k, n )

READ T <- A GETRF( k )
RW   C <- (k == 0) ? descA( k, n ) : C GEMM( k-1, k, n )
       -> descA( k, n )
       -> B GEMM( k, k+1 .. MT-1, n )

; (KT - k) * 100

BODY [type=tpu]
{
    C = ops.trsm_lower_unit(T, C)
}
END

TRSM_U(k, m)

k = 0 .. KT-1
m = k+1 .. MT-1

: descA( m, k )

READ T <- A GETRF( k )
RW   C <- (k == 0) ? descA( m, k ) : C GEMM( k-1, m, k )
       -> descA( m, k )
       -> A GEMM( k, m, k+1 .. NT-1 )

; (KT - k) * 100

BODY [type=tpu]
{
    C = ops.trsm_upper_right(T, C)
}
END

GEMM(k, m, n)

k = 0 .. KT-1
m = k+1 .. MT-1
n = k+1 .. NT-1

: descA( m, n )

READ A <- C TRSM_U( k, m )
READ B <- C TRSM_L( k, n )
RW   C <- (k == 0) ? descA( m, n ) : C GEMM( k-1, m, n )
       -> ((m == k+1) and (n == k+1)) ? A GETRF( k+1 )
       -> ((m == k+1) and (n > k+1)) ? C TRSM_L( k+1, n )
       -> ((m > k+1) and (n == k+1)) ? C TRSM_U( k+1, m )
       -> ((m > k+1) and (n > k+1)) ? C GEMM( k+1, m, n )

; (KT - k) * 10

BODY [type=tpu]
{
    C = ops.gemm_nn_sub(C, A, B)
}
END
"""

_factory = None


def dgetrf_factory() -> "ptg.JDFFactory":
    global _factory
    if _factory is None:
        _factory = ptg.compile_jdf(DGETRF_JDF, name="dgetrf_nopiv")
    return _factory


def dgetrf_nopiv_taskpool(A: TiledMatrix, rank: int = 0, nb_ranks: int = 1):
    from .. import ops as ops_module
    kt = min(A.mt, A.nt)
    # every diagonal tile must be square (triangular solves need a square
    # factor): square full tiles, and a square trailing tile if partial
    last_rows = A.lm - (kt - 1) * A.mb
    last_cols = A.ln - (kt - 1) * A.nb
    if A.mb != A.nb or min(last_rows, A.mb) != min(last_cols, A.nb):
        raise ValueError(
            f"dgetrf_nopiv needs square diagonal tiles; got mb={A.mb} "
            f"nb={A.nb}, trailing diagonal tile "
            f"{min(last_rows, A.mb)}x{min(last_cols, A.nb)}")
    tp = dgetrf_factory().new(descA=A, MT=A.mt, NT=A.nt, KT=kt,
                              rank=rank, nb_ranks=nb_ranks)
    tp.global_env["ops"] = ops_module
    return tp


def dgetrf_nopiv(context, A: TiledMatrix, rank: int = 0,
                 nb_ranks: int = 1) -> None:
    """Factor A = L U in place (no pivoting): unit-lower L strictly below
    the diagonal, U on and above. Blocking: enqueue + wait."""
    run_blocking(context, "dgetrf_nopiv",
                 [dgetrf_nopiv_taskpool(A, rank=rank, nb_ranks=nb_ranks)])


def dgetrf(A: np.ndarray, nb: int = 256):
    """Blocked LU with partial pivoting: ``A = P L U`` (general matrices,
    no diagonal-dominance requirement) on a numpy array, outside the
    runtime: no context, scheduler or device module sees it.  The same
    factorization as a task graph on the runtime's normal path is
    ``ops.dgetrf_1d(ctx, A)`` (ops/dgetrf_1d.py, DPLASMA's
    ``dgetrf_1d``).

    This one is a single jitted XLA program — the panel factorization
    via ``lax.linalg.lu`` (on the TPU XLA's pivoted LU custom call,
    which the v5e's compiler refuses from 16384 rows on), triangular
    solves for the block row, and one large MXU GEMM per trailing
    update; the panel loop is unrolled at trace time
    (problem-size-static, like a captured taskpool).

    Returns ``(LU, piv)``: packed factors (unit-lower L strictly below
    the diagonal, U on/above) and the pivot ROW PERMUTATION vector —
    ``A[piv] == L @ U``.

    Compile-time caveat: the panel loop is unrolled at trace time, so
    trace+compile cost and program size grow linearly with
    ``kt = ceil(min(m, n)/nb)`` (each step carries O(N^2) gather/scatter
    updates). Keep kt modest (tens, not hundreds) — e.g. raise ``nb``
    with N; ``_dgetrf_jit``'s lru_cache only hides *repeat* costs per
    distinct (shape, nb, dtype).
    """
    LU, perm = _dgetrf_jit(A.shape, nb, np.dtype(A.dtype).name)(A)
    return LU, perm


@functools.lru_cache(maxsize=64)
def _dgetrf_jit(shape, nb: int, dtype_name: str):
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_rows, n_cols = shape
    kt = (min(n_rows, n_cols) + nb - 1) // nb

    def fac(M):
        LU = M
        perm = jnp.arange(n_rows)
        for k in range(kt):
            k0 = k * nb
            # panel columns stop at the diagonal extent: for wide
            # matrices (n_rows < n_cols) the columns beyond row count
            # belong to the block row, not the factored panel
            k1 = min(k0 + nb, n_rows, n_cols)
            # panel: all rows below k0, this block column
            panel = LU[k0:, k0:k1]
            p_lu, p_piv, p_perm = lax.linalg.lu(panel)
            # apply the panel's row permutation to the whole trailing
            # rows (left factors + trailing columns) and the perm vector
            rows = LU[k0:]
            rows = rows.at[:, k0:k1].set(p_lu)
            rows = rows.at[:, :k0].set(rows[:, :k0][p_perm])
            rows = rows.at[:, k1:].set(rows[:, k1:][p_perm])
            LU = LU.at[k0:].set(rows)
            perm = perm.at[k0:].set(perm[k0:][p_perm])
            if k1 < n_cols:
                L11 = jnp.tril(LU[k0:k1, k0:k1], -1) + jnp.eye(
                    k1 - k0, dtype=M.dtype)
                U12 = lax.linalg.triangular_solve(
                    L11, LU[k0:k1, k1:], left_side=True, lower=True,
                    unit_diagonal=True)
                LU = LU.at[k0:k1, k1:].set(U12)
                if k1 < n_rows:
                    L21 = LU[k1:, k0:k1]
                    # true-f32 inputs (HIGHEST): unlike a lone GEMM,
                    # LU feeds each update into the next panel, so the
                    # MXU's default bf16-input pass compounds to ~1e-1
                    # relative error at n=4096 (measured)
                    acc = jnp.promote_types(M.dtype, jnp.float32)
                    LU = LU.at[k1:, k1:].add(
                        -jnp.matmul(L21, U12,
                                    precision=lax.Precision.HIGHEST,
                                    preferred_element_type=acc)
                        .astype(M.dtype))
        return LU, perm

    return jax.jit(fac)


def make_diag_dominant(m: int, n: int = None, dtype=np.float32,
                       seed: int = 0) -> np.ndarray:
    """A diagonally-dominant matrix — LU-stable without pivoting."""
    n = m if n is None else n
    rng = np.random.RandomState(seed)
    A = rng.rand(m, n).astype(np.float64) - 0.5
    for i in range(min(m, n)):
        A[i, i] = np.sum(np.abs(A[i])) + 1.0
    return A.astype(dtype)
