"""Reference for the inverse of an SPD matrix: M B = I, B symmetric
with the result's lower triangle."""
import numpy as np

from . import cholesky
from .blocks import over_rows
from .rounding import matmul

#: the input is cholesky's: I + W W', condition number about n / 64 + 1
make_input = cholesky.make_input


def seeded_w(n, seed):
    """The float64 W of ``make_input(n, seed)`` (the same draws)."""
    rng = np.random.default_rng(seed)
    W = (rng.random((n, cholesky.RANK), dtype=np.float32)
         - np.float32(0.5)) * np.float32((12.0 / cholesky.RANK) ** 0.5)
    return W.astype(np.float64)


def closed_form(n, seed):
    """(I + W W')^-1 = I - W (I + W' W)^-1 W' in float64 (Woodbury): the
    inverse no factorization touches.  ``make_input`` rounds I + W W'
    to float32, so this is the inverse of the input to about
    (n / 64 + 1) float32 roundings."""
    W = seeded_w(n, seed)
    small = np.eye(cholesky.RANK) + W.T @ W
    return np.eye(n) - W @ np.linalg.solve(small, W.T)


def expected(M, seed):
    n = M.shape[0]
    X = np.random.default_rng(seed + 1).standard_normal((n, 3))
    return {"X": X, "M": M}


def apply_symmetric(result, X):
    """B X in float64, B the symmetric matrix whose lower triangle is
    ``result``'s; row blocks, no n x n float64 copy."""
    n = X.shape[0]

    def lower(r0, r1):              # rows r0:r1 of tril(result), cols 0:r1
        Lb = result[r0:r1, :r1].astype(np.float64)
        Lb[:, r0:] = np.tril(Lb[:, r0:])
        return Lb

    def strict_t(r0, r1):           # (strictly lower rows r0:r1)' X[r0:r1]
        Lb = lower(r0, r1)
        Lb[:, r0:] = np.tril(Lb[:, r0:], -1)
        return np.pad(Lb.T @ X[r0:r1], ((0, n - r1), (0, 0)))

    low = np.concatenate(over_rows(lambda r0, r1: lower(r0, r1) @ X[:r1], n))
    return low + sum(over_rows(strict_t, n))


def residual(result, exp):
    """max over the seeded x of ||M (B x) - x|| / ||x||."""
    X, M = exp["X"], exp["M"]
    n = X.shape[0]
    Y = apply_symmetric(result, X)
    got = np.concatenate(over_rows(
        lambda r0, r1: M[r0:r1].astype(np.float64) @ Y, n))
    return float((np.linalg.norm(got - X, axis=0)
                  / np.linalg.norm(X, axis=0)).max())


def plain_factor(M, nb, precision="highest"):
    """The lower triangle of M^-1 by the three tile algorithms, one
    tile operation at a time (Cholesky, PLASMA's pdtrtri and pdlauum
    loops, lower, in place); the operands of every tile product are
    rounded to ``precision``.  The chip solves a triangular system by
    inverting diagonal blocks and multiplying, so a solve here is a
    product with the float64 inverse of the diagonal tile, rounded."""
    A = cholesky.plain_factor(M, nb, precision)
    nt = A.shape[0] // nb

    def t(i, j):
        return A[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]

    def inv_lower(x):
        return np.linalg.inv(np.tril(x).astype(np.float64)).astype(np.float32)

    for k in range(nt):                         # L <- L^-1
        inv_t = inv_lower(t(k, k))
        for m in range(k + 1, nt):
            t(m, k)[:] = -matmul(t(m, k), inv_t, precision)
        for m in range(k + 1, nt):
            for n in range(k):
                t(m, n)[:] += matmul(t(m, k), t(k, n), precision)
        for n in range(k):
            t(k, n)[:] = matmul(inv_t, t(k, n), precision)
        t(k, k)[:] = inv_t
    for k in range(nt):                         # L <- L' L, lower
        for n in range(k):
            t(n, n)[:] += matmul(t(k, n).T, t(k, n), precision)
            for m in range(n + 1, k):
                t(m, n)[:] += matmul(t(k, m).T, t(k, n), precision)
        lt = np.tril(t(k, k)).T
        for n in range(k):
            t(k, n)[:] = matmul(lt, t(k, n), precision)
        t(k, k)[:] = matmul(lt, lt.T, precision)
    return A
