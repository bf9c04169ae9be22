"""Tile QR / LU / GEMM PTG correctness (the widened DPLASMA slice).

References: DPLASMA's zgeqrf/zgetrf_nopiv/zgemm JDFs running on the
reference runtime; verification patterns follow the reference's check
programs (factor, then reconstruct and compare).
"""
import numpy as np
import pytest

import parsec_tpu
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.ops import (dgeqrf_taskpool, dgetrf_nopiv_taskpool,
                            make_diag_dominant, pdgemm_taskpool)


def _run(ctx, tp):
    ctx.add_taskpool(tp)
    ctx.wait()
    assert tp.completed


# --------------------------------------------------------------------- #
# QR                                                                    #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("m,n,nb", [(96, 96, 32), (64, 64, 64),
                                    (128, 64, 32), (96, 128, 32)])
def test_dgeqrf_rtr_identity(ctx, m, n, nb):
    """R^T R == A^T A characterizes the QR triangle independently of the
    per-row sign convention (and of Q, which dgeqrf discards)."""
    rng = np.random.RandomState(7)
    M = (rng.rand(m, n) - 0.5).astype(np.float32)
    A = TwoDimBlockCyclic(m, n, nb, nb, dtype=np.float32).from_numpy(M)
    _run(ctx, dgeqrf_taskpool(A))
    R = np.triu(A.to_numpy())
    np.testing.assert_allclose(
        R.T @ R, M.astype(np.float64).T @ M.astype(np.float64), atol=2e-3)


def test_dgeqrf_residual_gate(ctx):
    """The dgeqrf RESIDUAL gate (ISSUE 12 satellite): the second
    workload holds a strict relative residual bound (the number
    perfbench's dgeqrf cells check on the chip, at a CPU size) — the
    absolute tolerances above pass long after relative accuracy
    rots."""
    n, nb = 256, 64
    rng = np.random.RandomState(7)
    M = rng.rand(n, n).astype(np.float32)
    A = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)
    _run(ctx, dgeqrf_taskpool(A))
    R = np.triu(A.to_numpy()).astype(np.float64)
    G = M.astype(np.float64).T @ M.astype(np.float64)
    resid = np.abs(R.T @ R - G).max() / np.abs(G).max()
    assert resid < 1e-5, f"dgeqrf relative residual {resid:.2e}"


def test_dgeqrf_below_diagonal_zeroed(ctx):
    rng = np.random.RandomState(3)
    M = (rng.rand(96, 96) - 0.5).astype(np.float32)
    A = TwoDimBlockCyclic(96, 96, 32, 32, dtype=np.float32).from_numpy(M)
    _run(ctx, dgeqrf_taskpool(A))
    out = A.to_numpy()
    np.testing.assert_allclose(np.tril(out, -1), 0.0, atol=1e-5)


def test_dgeqrf_single_tile_matches_numpy(ctx):
    rng = np.random.RandomState(11)
    M = (rng.rand(48, 48) - 0.5).astype(np.float32)
    A = TwoDimBlockCyclic(48, 48, 48, 48, dtype=np.float32).from_numpy(M)
    _run(ctx, dgeqrf_taskpool(A))
    Rref = np.linalg.qr(M.astype(np.float64))[1]
    np.testing.assert_allclose(np.abs(np.triu(A.to_numpy())),
                               np.abs(Rref), atol=2e-3)


def test_dgeqrf_partial_edge_tiles(ctx):
    """Ragged edges factor correctly (Q scratch shapes are computed per
    instance from the tile geometry)."""
    rng = np.random.RandomState(13)
    M = (rng.rand(100, 100) - 0.5).astype(np.float32)
    A = TwoDimBlockCyclic(100, 100, 32, 32, dtype=np.float32).from_numpy(M)
    _run(ctx, dgeqrf_taskpool(A))
    R = np.triu(A.to_numpy())
    np.testing.assert_allclose(
        R.T @ R, M.astype(np.float64).T @ M.astype(np.float64), atol=2e-3)


def test_dgeqrf_rejects_nonsquare_diag_tiles(ctx):
    # trailing diagonal tile 32x26: not factorable panel-wise
    with pytest.raises(ValueError):
        dgeqrf_taskpool(TwoDimBlockCyclic(100, 90, 32, 32, dtype=np.float32))
    with pytest.raises(ValueError):
        dgeqrf_taskpool(TwoDimBlockCyclic(64, 64, 32, 16, dtype=np.float32))


# --------------------------------------------------------------------- #
# LU (no pivoting)                                                      #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("m,n,nb", [(96, 96, 32), (64, 64, 64), (100, 100, 32)])
def test_dgetrf_nopiv_reconstructs(ctx, m, n, nb):
    M = make_diag_dominant(m, n)
    A = TwoDimBlockCyclic(m, n, nb, nb, dtype=np.float32).from_numpy(M)
    _run(ctx, dgetrf_nopiv_taskpool(A))
    out = A.to_numpy().astype(np.float64)
    L = np.tril(out, -1) + np.eye(m, n)
    U = np.triu(out)
    np.testing.assert_allclose(L @ U, M.astype(np.float64),
                               rtol=0, atol=5e-3)


def test_dgetrf_nopiv_batched_dispatch_bit_exact():
    """Batched (unroll) device dispatch must be bit-exact vs per-task
    for the LU task classes too (ISSUE 5 acceptance)."""
    import parsec_tpu
    from parsec_tpu.utils.params import params

    M = make_diag_dominant(128, 128)

    def run(batch_max):
        with params.cmdline_override("device_batch_max", str(batch_max)), \
             params.cmdline_override("device_tpu_max", "1"):
            c = parsec_tpu.init(nb_cores=2)
            try:
                A = TwoDimBlockCyclic(128, 128, 32, 32,
                                      dtype=np.float32).from_numpy(M.copy())
                _run(c, dgetrf_nopiv_taskpool(A))
                return A.to_numpy()
            finally:
                c.fini()

    np.testing.assert_array_equal(run(16), run(1))


def test_dgetrf_nopiv_single_tile_matches_scipy(ctx):
    import scipy.linalg
    M = make_diag_dominant(40)
    A = TwoDimBlockCyclic(40, 40, 40, 40, dtype=np.float32).from_numpy(M)
    _run(ctx, dgetrf_nopiv_taskpool(A))
    out = A.to_numpy().astype(np.float64)
    # diagonally dominant => scipy's pivoted LU does not permute
    P, L, U = scipy.linalg.lu(M.astype(np.float64))
    np.testing.assert_allclose(P, np.eye(40))
    np.testing.assert_allclose(np.tril(out, -1), np.tril(L, -1), atol=1e-3)
    np.testing.assert_allclose(np.triu(out), U, atol=1e-3)


# --------------------------------------------------------------------- #
# GEMM                                                                  #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("m,n,k,nb", [(96, 64, 128, 32), (64, 64, 64, 64),
                                      (100, 60, 84, 32)])
def test_pdgemm_matches_numpy(ctx, m, n, k, nb):
    rng = np.random.RandomState(5)
    Am = (rng.rand(m, k) - 0.5).astype(np.float32)
    Bm = (rng.rand(k, n) - 0.5).astype(np.float32)
    Cm = (rng.rand(m, n) - 0.5).astype(np.float32)
    A = TwoDimBlockCyclic(m, k, nb, nb, dtype=np.float32).from_numpy(Am)
    B = TwoDimBlockCyclic(k, n, nb, nb, dtype=np.float32).from_numpy(Bm)
    C = TwoDimBlockCyclic(m, n, nb, nb, dtype=np.float32).from_numpy(Cm)
    _run(ctx, pdgemm_taskpool(A, B, C, alpha=2.0, beta=-1.0))
    ref = 2.0 * (Am.astype(np.float64) @ Bm.astype(np.float64)) - Cm
    np.testing.assert_allclose(C.to_numpy(), ref, atol=2e-3)


def test_pdgemm_shape_mismatch_rejected(ctx):
    A = TwoDimBlockCyclic(64, 64, 32, 32)
    B = TwoDimBlockCyclic(32, 64, 32, 32)
    C = TwoDimBlockCyclic(64, 64, 32, 32)
    with pytest.raises(ValueError):
        pdgemm_taskpool(A, B, C)
    # grids conform but element extents don't (last k-tile 20 vs 26)
    A2 = TwoDimBlockCyclic(64, 84, 32, 32)
    B2 = TwoDimBlockCyclic(90, 64, 32, 32)
    with pytest.raises(ValueError):
        pdgemm_taskpool(A2, B2, C)


def test_dgetrf_rejects_nonsquare_diag_tiles(ctx):
    with pytest.raises(ValueError):
        dgetrf_nopiv_taskpool(TwoDimBlockCyclic(100, 90, 32, 32))
    with pytest.raises(ValueError):
        dgetrf_nopiv_taskpool(TwoDimBlockCyclic(64, 64, 32, 16))


def test_pdgemm_multirank_distributed():
    """SUMMA across 4 ranks over the in-process fabric: each rank owns only
    its block-cyclic tiles; A/B tiles reach consumers via READ_A/READ_B
    broadcast task edges (no cross-rank memory reads)."""
    from conftest import spmd
    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.ops import pdgemm_factory
    from parsec_tpu import ops as ops_module

    nb_ranks, P, Q = 4, 2, 2
    m, n, k, nb = 128, 96, 64, 32
    rng = np.random.RandomState(9)
    Am = (rng.rand(m, k) - 0.5).astype(np.float32)
    Bm = (rng.rand(k, n) - 0.5).astype(np.float32)
    Cm = (rng.rand(m, n) - 0.5).astype(np.float32)

    def rank_fn(rank, fabric):
        import parsec_tpu
        eng = RemoteDepEngine(fabric.engine(rank))
        c = parsec_tpu.Context(nb_cores=1, comm=eng, enable_tpu=False)
        try:
            def dist(lm, ln, M):
                d = TwoDimBlockCyclic(lm, ln, nb, nb, P=P, Q=Q,
                                      nodes=nb_ranks, rank=rank,
                                      dtype=np.float32)
                # populate only locally-owned tiles (true distribution)
                for i in range(d.mt):
                    for j in range(d.nt):
                        if d.rank_of(i, j) == rank:
                            np.copyto(
                                d.tile(i, j),
                                M[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb])
                return d
            A, B, C = dist(m, k, Am), dist(k, n, Bm), dist(m, n, Cm)
            A.name, B.name, C.name = "descA", "descB", "descC"
            tp = pdgemm_factory().new(
                descA=A, descB=B, descC=C, MT=C.mt, NT=C.nt, KT=A.nt,
                ALPHA=1.0, BETA=1.0, rank=rank, nb_ranks=nb_ranks)
            tp.global_env["ops"] = ops_module
            c.add_taskpool(tp)
            c.wait()
            local = {}
            for i in range(C.mt):
                for j in range(C.nt):
                    if C.rank_of(i, j) == rank:
                        local[(i, j)] = np.array(C.tile(i, j))
            return local
        finally:
            c.fini()

    out, _fabric = spmd(nb_ranks, rank_fn)
    ref = Am.astype(np.float64) @ Bm.astype(np.float64) + Cm
    got = np.zeros((m, n))
    for local in out:
        for (i, j), tile in local.items():
            got[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb] = tile
    np.testing.assert_allclose(got, ref, atol=2e-3)


# --------------------------------------------------------------------- #
# triangular solves + dposv                                             #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("n,nrhs,nb", [(96, 32, 32), (64, 64, 64),
                                       (128, 96, 32)])
def test_dposv_solves(ctx, n, nrhs, nb):
    from parsec_tpu.ops import dposv, make_spd
    M = make_spd(n)
    rng = np.random.RandomState(1)
    Bm = (rng.rand(n, nrhs) - 0.5).astype(np.float32)
    A = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)
    B = TwoDimBlockCyclic(n, nrhs, nb, nb, dtype=np.float32).from_numpy(Bm)
    dposv(ctx, A, B)
    ref = np.linalg.solve(M.astype(np.float64), Bm.astype(np.float64))
    np.testing.assert_allclose(B.to_numpy(), ref, atol=5e-3)


def test_dtrsm_forward_matches_scipy(ctx):
    import scipy.linalg
    from parsec_tpu.ops import dtrsm_lower_taskpool
    rng = np.random.RandomState(2)
    Lm = np.tril(rng.rand(96, 96).astype(np.float32)) + 4 * np.eye(96,
                                                                   dtype=np.float32)
    Bm = (rng.rand(96, 64) - 0.5).astype(np.float32)
    L = TwoDimBlockCyclic(96, 96, 32, 32, dtype=np.float32).from_numpy(Lm)
    B = TwoDimBlockCyclic(96, 64, 32, 32, dtype=np.float32).from_numpy(Bm)
    _run(ctx, dtrsm_lower_taskpool(L, B))
    ref = scipy.linalg.solve_triangular(Lm.astype(np.float64),
                                        Bm.astype(np.float64), lower=True)
    np.testing.assert_allclose(B.to_numpy(), ref, atol=2e-3)


def test_dtrsm_backward_matches_scipy(ctx):
    import scipy.linalg
    from parsec_tpu.ops import dtrsm_lower_trans_taskpool
    rng = np.random.RandomState(3)
    Lm = np.tril(rng.rand(96, 96).astype(np.float32)) + 4 * np.eye(96,
                                                                   dtype=np.float32)
    Bm = (rng.rand(96, 32) - 0.5).astype(np.float32)
    L = TwoDimBlockCyclic(96, 96, 32, 32, dtype=np.float32).from_numpy(Lm)
    B = TwoDimBlockCyclic(96, 32, 32, 32, dtype=np.float32).from_numpy(Bm)
    _run(ctx, dtrsm_lower_trans_taskpool(L, B))
    ref = scipy.linalg.solve_triangular(Lm.astype(np.float64).T,
                                        Bm.astype(np.float64), lower=False)
    np.testing.assert_allclose(B.to_numpy(), ref, atol=2e-3)


def test_dtrsm_shape_mismatch(ctx):
    from parsec_tpu.ops import dtrsm_lower_taskpool
    with pytest.raises(ValueError):
        dtrsm_lower_taskpool(TwoDimBlockCyclic(64, 96, 32, 32),
                             TwoDimBlockCyclic(64, 32, 32, 32))


def test_dposv_multirank_distributed():
    """dposv across 4 ranks: the factorization writes affinity tiles only
    and the solves' L tiles travel via RDIAG/RPANEL broadcast reader
    edges — no cross-rank memory reads."""
    from conftest import spmd
    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.ops import dposv, make_spd

    nb_ranks, n, nrhs, nb = 4, 128, 32, 32
    M = make_spd(n)
    rng = np.random.RandomState(4)
    Bm = (rng.rand(n, nrhs) - 0.5).astype(np.float32)

    def rank_fn(rank, fabric):
        import parsec_tpu
        eng = RemoteDepEngine(fabric.engine(rank))
        c = parsec_tpu.Context(nb_cores=1, comm=eng, enable_tpu=False)
        try:
            def dist(lm, ln, src, P, Q):
                d = TwoDimBlockCyclic(lm, ln, nb, nb, P=P, Q=Q,
                                      nodes=nb_ranks, rank=rank,
                                      dtype=np.float32)
                for (i, j) in d.local_tiles():
                    np.copyto(d.tile(i, j),
                              src[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb])
                return d
            A = dist(n, n, M, 2, 2)
            B = dist(n, nrhs, Bm, 4, 1)
            A.name, B.name = "descA", "descB"
            dposv(c, A, B, rank=rank, nb_ranks=nb_ranks)
            return {(i, j): np.array(B.tile(i, j))
                    for (i, j) in B.local_tiles()}
        finally:
            c.fini()

    results, fabric = spmd(nb_ranks, rank_fn)
    got = np.zeros((n, nrhs))
    for local in results:
        for (i, j), t in local.items():
            got[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb] = t
    ref = np.linalg.solve(M.astype(np.float64), Bm.astype(np.float64))
    np.testing.assert_allclose(got, ref, atol=5e-3)
    assert fabric.msg_count > 0


@pytest.mark.parametrize("transa,transb,m,n,k,nb", [
    ("t", "n", 96, 64, 80, 16), ("n", "t", 96, 64, 80, 16),
    ("t", "t", 96, 64, 80, 16),
    # ragged edge tiles under transposition
    ("t", "n", 100, 60, 84, 32), ("n", "t", 100, 60, 84, 32),
    ("t", "t", 100, 60, 84, 32)])
def test_pdgemm_transposes(ctx, transa, transb, m, n, k, nb):
    rng = np.random.RandomState(6)
    Am = (rng.rand(*((k, m) if transa == "t" else (m, k))) - 0.5).astype(
        np.float32)
    Bm = (rng.rand(*((n, k) if transb == "t" else (k, n))) - 0.5).astype(
        np.float32)
    Cm = (rng.rand(m, n) - 0.5).astype(np.float32)
    A = TwoDimBlockCyclic(*Am.shape, nb, nb, dtype=np.float32).from_numpy(Am)
    B = TwoDimBlockCyclic(*Bm.shape, nb, nb, dtype=np.float32).from_numpy(Bm)
    C = TwoDimBlockCyclic(m, n, nb, nb, dtype=np.float32).from_numpy(Cm)
    _run(ctx, pdgemm_taskpool(A, B, C, alpha=1.5, beta=0.5,
                              transa=transa, transb=transb))
    opA = Am.T if transa == "t" else Am
    opB = Bm.T if transb == "t" else Bm
    ref = 1.5 * (opA.astype(np.float64) @ opB.astype(np.float64)) + 0.5 * Cm
    np.testing.assert_allclose(C.to_numpy(), ref, atol=2e-3)


def test_pdgemm_bad_trans_rejected(ctx):
    A = TwoDimBlockCyclic(64, 64, 32, 32)
    with pytest.raises(ValueError, match="transa"):
        pdgemm_taskpool(A, A, A, transa="x")


def _spmd_factor(taskpool_factory, M, n, nb, nb_ranks=4):
    """Scatter M block-cyclically over nb_ranks, run the factorization
    SPMD over the in-process fabric, gather the local tiles back."""
    from conftest import spmd
    from parsec_tpu.comm import RemoteDepEngine

    # largest P with P | nb_ranks and P <= sqrt: a valid PxQ grid for any
    # rank count (4 -> 2x2, 2 -> 1x2, 6 -> 2x3)
    P = max(p for p in range(1, int(nb_ranks ** 0.5) + 1) if nb_ranks % p == 0)
    Q = nb_ranks // P

    def rank_fn(rank, fabric):
        import parsec_tpu
        eng = RemoteDepEngine(fabric.engine(rank))
        c = parsec_tpu.Context(nb_cores=1, comm=eng, enable_tpu=False)
        try:
            A = TwoDimBlockCyclic(n, n, nb, nb, P=P, Q=Q, nodes=nb_ranks,
                                  rank=rank, dtype=np.float32)
            A.name = "descA"
            for (i, j) in A.local_tiles():
                np.copyto(A.tile(i, j),
                          M[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb])
            tp = taskpool_factory(A, rank=rank, nb_ranks=nb_ranks)
            c.add_taskpool(tp)
            c.wait()
            return {(i, j): np.array(A.tile(i, j))
                    for (i, j) in A.local_tiles()}
        finally:
            c.fini()

    results, _ = spmd(nb_ranks, rank_fn)
    got = np.zeros((n, n), np.float64)
    for local in results:
        for (i, j), t in local.items():
            got[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb] = t
    return got


def test_dgeqrf_multirank_distributed():
    """QR across 4 ranks. The R triangle returns to descA(k,k) from the
    END of each TSQRT chain — a cross-rank memory writeback."""
    rng = np.random.RandomState(21)
    M = (rng.rand(128, 128) - 0.5).astype(np.float32)
    got = _spmd_factor(dgeqrf_taskpool, M, 128, 32)
    R = np.triu(got)
    ref = M.astype(np.float64).T @ M.astype(np.float64)
    np.testing.assert_allclose(R.T @ R, ref, atol=2e-3)


def test_dgetrf_multirank_distributed():
    """LU across 4 ranks (all writes are affinity-local; panels travel
    task edges)."""
    n = 128
    M = make_diag_dominant(n)
    got = _spmd_factor(dgetrf_nopiv_taskpool, M, n, 32)
    L = np.tril(got, -1) + np.eye(n)
    U = np.triu(got)
    np.testing.assert_allclose(L @ U, M.astype(np.float64), atol=5e-3)


def test_dgetrf_partial_pivoting():
    """Pivoted blocked LU (ops.dgetrf): A[piv] == L U for a general
    (non-diagonally-dominant) matrix the nopiv variant cannot factor
    stably."""
    from parsec_tpu.ops import dgetrf

    n, nb = 192, 64
    rng = np.random.RandomState(11)
    A = (rng.rand(n, n) - 0.5).astype(np.float32)  # no dominance
    LU, piv = dgetrf(A, nb=nb)
    LU = np.asarray(LU)
    L = np.tril(LU, -1) + np.eye(n, dtype=np.float32)
    U = np.triu(LU)
    assert np.linalg.norm(A[np.asarray(piv)] - L @ U) \
        / np.linalg.norm(A) < 1e-5
    # pivoting actually happened (a random matrix always needs swaps)
    assert not np.array_equal(np.asarray(piv), np.arange(n))


def test_dgetrf_rectangular():
    from parsec_tpu.ops import dgetrf

    m, n, nb = 160, 96, 64
    rng = np.random.RandomState(12)
    A = (rng.rand(m, n) - 0.5).astype(np.float32)
    LU, piv = dgetrf(A, nb=nb)
    LU = np.asarray(LU)
    L = np.tril(LU, -1)[:, :n] + np.eye(m, n, dtype=np.float32)
    U = np.triu(LU)[:n]
    assert np.linalg.norm(A[np.asarray(piv)] - L @ U) \
        / np.linalg.norm(A) < 1e-5


def test_dgetrf_wide():
    from parsec_tpu.ops import dgetrf

    m, n, nb = 96, 160, 64
    rng = np.random.RandomState(13)
    A = (rng.rand(m, n) - 0.5).astype(np.float32)
    LU, piv = dgetrf(A, nb=nb)
    LU = np.asarray(LU)
    L = np.tril(LU, -1)[:, :m] + np.eye(m, dtype=np.float32)
    U = np.triu(LU)
    assert np.linalg.norm(A[np.asarray(piv)] - L @ U) \
        / np.linalg.norm(A) < 1e-5


# --------------------------------------------------------------------- #
# inverses / solves (the potri family + gesv)                           #
# --------------------------------------------------------------------- #
def test_dtrtri_inverse(ctx):
    """The tiled triangular inverse (ops/dpoinv.py): L^-1 in place on
    the lower tiles, through the runtime."""
    from parsec_tpu.ops import dtrtri

    n, nb = 96, 32
    rng = np.random.RandomState(21)
    L = np.tril(rng.rand(n, n).astype(np.float32)) + 2 * np.eye(
        n, dtype=np.float32)
    A = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(L)
    dtrtri(ctx, A)
    Linv = np.tril(A.to_numpy())
    np.testing.assert_allclose(Linv @ L, np.eye(n), atol=2e-4)
    np.testing.assert_allclose(L @ Linv, np.eye(n), atol=2e-4)


def test_dpotri_spd_inverse_from_cholesky(ctx):
    """potrf (PTG) then potri (dtrtri composed with dlauum): the full
    DPLASMA zpotri pipeline, every tile operation a task."""
    from parsec_tpu.ops import dpotri, dpotrf_taskpool, make_spd

    n, nb = 128, 64
    M = make_spd(n, seed=22)
    A = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)
    _run(ctx, dpotrf_taskpool(A))
    dpotri(ctx, A)
    low = np.tril(A.to_numpy())
    Ainv = low + np.tril(low, -1).T
    np.testing.assert_allclose(Ainv @ M, np.eye(n), atol=5e-3)


def test_dgesv_general_solve():
    from parsec_tpu.ops import dgesv

    n, nrhs = 160, 8
    rng = np.random.RandomState(23)
    A = (rng.rand(n, n) - 0.5).astype(np.float32)
    B = rng.rand(n, nrhs).astype(np.float32)
    X = np.asarray(dgesv(A, B, nb=64))
    ref = np.linalg.solve(A.astype(np.float64), B.astype(np.float64))
    assert np.abs(X - ref).max() < 5e-2
