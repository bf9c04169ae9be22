"""Reference for the general matrix product C <- alpha A B + beta C."""
import numpy as np

from .blocks import BLOCK, over_rows
from .rounding import matmul

#: the scalars of ``operations/dgemm.json``'s ``args``; the reference
#: takes nothing of the program, so it states them itself
ALPHA = 0.51
BETA = -0.42

OPERANDS = ("A", "B", "C")


def make_input(n, seed):
    """A, B and C, n x n, entries uniform in [-0.5, 0.5) (the range of
    DPLASMA's dplrnt): each row block from a generator of its own,
    seeded by (seed, operand, block), so that the blocks are made on
    threads and the same seed gives the same matrices."""
    def fill(M, which):
        def block(r0, r1):
            rng = np.random.default_rng([seed, which, r0 // BLOCK])
            rng.random(out=M[r0:r1], dtype=np.float32)
            M[r0:r1] -= np.float32(0.5)
        over_rows(block, n)
        return M

    return {name: fill(np.empty((n, n), dtype=np.float32), i)
            for i, name in enumerate(OPERANDS)}


def _times(M, X):
    """M X in float64, M a float32 matrix taken a row block at a time."""
    n = M.shape[0]
    return np.concatenate(over_rows(
        lambda r0, r1: M[r0:r1].astype(np.float64) @ X, n))


def expected(inputs, seed):
    n = inputs["C"].shape[0]
    X = np.random.default_rng(seed + 1).standard_normal((n, 3))
    want = ALPHA * _times(inputs["A"], _times(inputs["B"], X)) \
        + BETA * _times(inputs["C"], X)
    return {"X": X, "want": want}


def residual(C_out, exp):
    """max over the seeded x of ||C_out x - (alpha A (B x) + beta C x)||
    / ||alpha A (B x) + beta C x||, C_out the product pulled from the
    chip, A, B, C the inputs."""
    got = _times(C_out, exp["X"])
    return float((np.linalg.norm(got - exp["want"], axis=0)
                  / np.linalg.norm(exp["want"], axis=0)).max())


def plain_product(inputs, nb, precision="highest"):
    """The tile product written plainly, one tile operation at a time
    and the chain over k in order: C(m, n) <- beta C(m, n) + alpha
    A(m, 0) B(0, n), then C(m, n) <- C(m, n) + alpha A(m, k) B(k, n);
    the operands of every tile product are rounded to ``precision``,
    the accumulation is float32."""
    A, B = inputs["A"], inputs["B"]
    C = np.array(inputs["C"], dtype=np.float32)
    nt = C.shape[0] // nb
    alpha, beta = np.float32(ALPHA), np.float32(BETA)

    def t(M, i, j):
        return M[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]

    for m in range(nt):
        for n in range(nt):
            c = t(C, m, n)
            c *= beta
            for k in range(nt):
                c += alpha * matmul(t(A, m, k), t(B, k, n), precision)
    return C
