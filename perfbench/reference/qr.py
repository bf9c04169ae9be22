"""Reference for the R factor of A = Q R (Q discarded): R'R = A'A."""
import numpy as np

from .blocks import over_rows
from .rounding import matmul


def make_input(n, seed):
    rng = np.random.default_rng(seed)
    return rng.random((n, n), dtype=np.float32) - np.float32(0.5)


def _gram_apply(rows, X, n):
    """sum over row blocks of B' (B X), B = rows(r0, r1) in float64."""
    def one(r0, r1):
        B = rows(r0, r1)
        return B.T @ (B @ X[:B.shape[1]]) if B.shape[1] == X.shape[0] \
            else np.pad(B.T @ (B @ X[-B.shape[1]:]),
                        ((X.shape[0] - B.shape[1], 0), (0, 0)))
    return sum(over_rows(one, n))


def expected(M, seed):
    n = M.shape[1]
    X = np.random.default_rng(seed + 1).standard_normal((n, 3))
    G = _gram_apply(lambda r0, r1: M[r0:r1].astype(np.float64), X, n)
    return {"X": X, "AtAX": G}


def residual(factor, exp):
    """max over the seeded x of ||R'(Rx) - A'(Ax)|| / ||A'(Ax)||, R the
    upper triangle of ``factor``."""
    X, G = exp["X"], exp["AtAX"]
    n = X.shape[0]

    def upper(r0, r1):              # rows r0:r1 of R, columns r0:n
        Rb = factor[r0:r1, r0:].astype(np.float64)
        Rb[:, :r1 - r0] = np.triu(Rb[:, :r1 - r0])
        return Rb

    got = _gram_apply(upper, X, n)
    return float((np.linalg.norm(got - G, axis=0)
                  / np.linalg.norm(G, axis=0)).max())


def plain_factor(M, nb, precision="highest"):
    """Flat-tree tile QR with explicit orthogonal factors, one tile
    operation at a time (GEQRT, UNMQR, TSQRT, TSMQR); the operands of
    every tile product are rounded to ``precision``."""
    A = np.array(M, dtype=np.float32)
    nt = A.shape[0] // nb

    def t(i, j):
        return A[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]

    for k in range(nt):
        q, r = np.linalg.qr(t(k, k), mode="complete")
        t(k, k)[:] = r
        for n in range(k + 1, nt):
            t(k, n)[:] = matmul(q.T, t(k, n), precision)
        for m in range(k + 1, nt):
            q2, rf = np.linalg.qr(np.vstack([t(k, k), t(m, k)]),
                                  mode="complete")
            t(k, k)[:] = rf[:nb]
            t(m, k)[:] = 0
            for n in range(k + 1, nt):
                s = matmul(q2.T, np.vstack([t(k, n), t(m, n)]), precision)
                t(k, n)[:] = s[:nb]
                t(m, n)[:] = s[nb:]
    return A
