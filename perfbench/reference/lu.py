"""Reference for LU with partial pivoting: P A = L U, so that
(LU)'(LU) = A'A whatever the row permutation P is."""
import numpy as np

from .blocks import over_rows
from .rounding import matmul

#: partial pivoting's guarantee, with room for one rounding of a
#: quotient of two float32 of equal magnitude
L_MAX = 1.0 + 2.0 ** -20


def make_input(n, seed):
    """A general matrix, entries uniform in [-0.5, 0.5): not diagonally
    dominant, so a factorization that skips pivoting divides by small
    diagonal entries and cannot pass."""
    rng = np.random.default_rng(seed)
    return rng.random((n, n), dtype=np.float32) - np.float32(0.5)


def _padded(block, r0, n):
    """``block`` (rows for r0:r0+len) under r0 and over the rest of n."""
    return np.pad(block, ((r0, n - r0 - block.shape[0]), (0, 0)))


def expected(M, seed):
    n = M.shape[1]
    X = np.random.default_rng(seed + 1).standard_normal((n, 3))

    def one(r0, r1):
        B = M[r0:r1].astype(np.float64)
        return B.T @ (B @ X)

    return {"X": X, "AtAX": sum(over_rows(one, n))}


def residual(factor, exp):
    """max over the seeded x of ||(LU)'((LU)x) - A'(Ax)|| / ||A'(Ax)||,
    L the unit lower and U the upper triangle of ``factor``; the check
    is handed the factor alone, and the number does not depend on the
    row permutation.  ``inf`` when any |l_ij| exceeds :data:`L_MAX`:
    that every multiplier is at most 1 in magnitude is what makes the
    pivoting partial pivoting, and is part of ``correct``."""
    X, G = exp["X"], exp["AtAX"]
    n = X.shape[0]

    def lower(r0, r1):              # rows r0:r1 of L, columns 0:r1
        Lb = factor[r0:r1, :r1].astype(np.float64)
        Lb[:, r0:] = np.tril(Lb[:, r0:], -1) + np.eye(r1 - r0)
        return Lb

    def upper(r0, r1):              # rows r0:r1 of U, columns r0:n
        Ub = factor[r0:r1, r0:].astype(np.float64)
        Ub[:, :r1 - r0] = np.triu(Ub[:, :r1 - r0])
        return Ub

    def largest_l(r0, r1):
        Lb = np.abs(factor[r0:r1, :r1])
        Lb[:, r0:] = np.tril(Lb[:, r0:], -1)
        return float(Lb.max())

    if not max(over_rows(largest_l, n)) <= L_MAX:     # a NaN fails too
        return float("inf")
    Y = np.concatenate(over_rows(                       # U x
        lambda r0, r1: upper(r0, r1) @ X[r0:], n))
    Z = np.concatenate(over_rows(                       # L (U x)
        lambda r0, r1: lower(r0, r1) @ Y[:r1], n))
    W = sum(over_rows(                                  # L' (L U x)
        lambda r0, r1: _padded(lower(r0, r1).T @ Z[r0:r1], 0, n), n))
    got = sum(over_rows(                                # U' (L' L U x)
        lambda r0, r1: _padded(upper(r0, r1).T @ W[r0:r1], r0, n), n))
    return float((np.linalg.norm(got - G, axis=0)
                  / np.linalg.norm(G, axis=0)).max())


def plain_factor(M, nb, precision="highest", with_pivots=False):
    """Right-looking blocked LU with partial pivoting, one block column
    at a time: the panel column by column (the pivot the entry of
    largest magnitude on or below the diagonal, the first such on a
    tie), its interchanges applied to the whole rows, the block row by
    forward substitution, the trailing update one product whose
    operands are rounded to ``precision``.  A float64 ``M`` is factored
    in float64 throughout, with no rounding: what the tests hold the
    program's pivots and factor against.  Returns the packed factor of
    P A (and the pivots, LAPACK's 0-based ipiv, on request)."""
    exact = np.asarray(M).dtype == np.float64
    A = np.array(M, dtype=np.float64 if exact else np.float32)
    n = A.shape[0]
    ipiv = np.arange(n)
    for k0 in range(0, min(A.shape), nb):
        k1 = min(k0 + nb, min(A.shape))
        for j in range(k0, k1):
            p = j + int(np.argmax(np.abs(A[j:, j])))
            ipiv[j] = p
            if p != j:
                A[[j, p]] = A[[p, j]]
            A[j + 1:, j] /= A[j, j]
            A[j + 1:, j + 1:k1] -= np.outer(A[j + 1:, j], A[j, j + 1:k1])
        if k1 < A.shape[1]:
            L11 = np.tril(A[k0:k1, k0:k1], -1).astype(np.float64) \
                + np.eye(k1 - k0)
            A[k0:k1, k1:] = np.linalg.solve(
                L11, A[k0:k1, k1:].astype(np.float64)).astype(A.dtype)
            A[k1:, k1:] -= A[k1:, k0:k1] @ A[k0:k1, k1:] if exact else \
                matmul(A[k1:, k0:k1], A[k0:k1, k1:], precision)
    return (A, ipiv) if with_pivots else A
