"""Reference for lower Cholesky: M = L L'."""
import numpy as np

from .blocks import over_rows
from .rounding import matmul

#: rank of the seeded part of the input
RANK = 64


def make_input(n, seed):
    """I + W W' with W n x RANK, entries uniform and scaled so that the
    diagonal of W W' is about 1 and its other entries about RANK**-0.5:
    SPD with condition number about n / RANK + 1, built in O(n^2 RANK)
    by float32 products on the host, a row block to a thread.

    Every Schur-complement update of its factorization is as large as
    the entries it updates, so the precision of the tile products shows
    in the residual.  (chip_smoke's symmetric + n*I does not show it:
    PR 23 read 5.835e-7 on the chip at 'highest', 'high' and 'default'
    alike, the float32 rounding of a diagonal of size n hiding all
    else.)"""
    rng = np.random.default_rng(seed)
    W = (rng.random((n, RANK), dtype=np.float32) - np.float32(0.5)) \
        * np.float32((12.0 / RANK) ** 0.5)
    Wt = np.ascontiguousarray(W.T)
    M = np.empty((n, n), dtype=np.float32)
    over_rows(lambda r0, r1: np.matmul(W[r0:r1], Wt, out=M[r0:r1]), n)
    M[np.diag_indices(n)] += np.float32(1.0)
    return M


def expected(M, seed):
    n = M.shape[0]
    X = np.random.default_rng(seed + 1).standard_normal((n, 3))
    MX = np.concatenate(over_rows(
        lambda r0, r1: M[r0:r1].astype(np.float64) @ X, n))
    return {"X": X, "MX": MX}


def residual(factor, exp):
    """max over the seeded x of ||L(L'x) - Mx|| / ||Mx||, L the lower
    triangle of ``factor``."""
    X, MX = exp["X"], exp["MX"]
    n = X.shape[0]

    def lower(r0, r1):              # rows r0:r1 of L, columns 0:r1
        Lb = factor[r0:r1, :r1].astype(np.float64)
        Lb[:, r0:] = np.tril(Lb[:, r0:])
        return Lb

    Y = sum(over_rows(lambda r0, r1: lower(r0, r1).T @ X[r0:r1]
                      if r1 == n else
                      np.pad(lower(r0, r1).T @ X[r0:r1],
                             ((0, n - r1), (0, 0))), n))       # L' X
    got = np.concatenate(over_rows(
        lambda r0, r1: lower(r0, r1) @ Y[:r1], n))
    return float((np.linalg.norm(got - MX, axis=0)
                  / np.linalg.norm(MX, axis=0)).max())


def plain_factor(M, nb, precision="highest"):
    """Right-looking tile Cholesky, one tile operation at a time; the
    operands of every tile product are rounded to ``precision``."""
    A = np.array(M, dtype=np.float32)
    nt = A.shape[0] // nb

    def t(i, j):
        return A[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]

    for k in range(nt):
        t(k, k)[:] = np.linalg.cholesky(t(k, k))
        # the chip solves a triangular system by inverting diagonal
        # blocks and multiplying, so the operand rounding applies
        inv_t = np.linalg.inv(
            np.tril(t(k, k)).astype(np.float64)).T.astype(np.float32)
        for m in range(k + 1, nt):
            t(m, k)[:] = matmul(t(m, k), inv_t, precision)
        for m in range(k + 1, nt):
            t(m, m)[:] -= matmul(t(m, k), t(m, k).T, precision)
            for n in range(k + 1, m):
                t(m, n)[:] -= matmul(t(m, k), t(n, k).T, precision)
    return A
