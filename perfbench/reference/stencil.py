"""Reference for the 1D stencil mini-app: ``iterations`` Jacobi steps of
a (2 ``radius`` + 1)-point weighted sum along every row of a matrix,
zero outside it."""
import numpy as np

from .blocks import BLOCK, over_rows
from .rounding import round_operand

#: the arguments of ``operations/stencil_1d.json`` and the weights the
#: entry point takes for that radius; the reference takes nothing of
#: the program, so it states them itself
ITERATIONS = 100
RADIUS = 1
WEIGHTS = (0.25, 0.5, 0.25)
#: whole rows replayed plainly, one from each of as many equal bands of
#: the rows: every tile row of 1/64 of the matrix or more holds one
ROWS = 64


def make_input(n, seed):
    """U_0, n x n, entries uniform in [-0.5, 0.5): each row block from a
    generator of its own, seeded by (seed, block), so that the blocks
    are made on threads and the same seed gives the same matrix."""
    U = np.empty((n, n), dtype=np.float32)

    def block(r0, r1):
        rng = np.random.default_rng([seed, r0 // BLOCK])
        rng.random(out=U[r0:r1], dtype=np.float32)
        U[r0:r1] -= np.float32(0.5)

    over_rows(block, n)
    return U


def plain(U0, iterations=ITERATIONS, radius=RADIUS, weights=WEIGHTS,
          rounded=None):
    """The loop written plainly, whole rows, no tiles, no ghosts:
    u_t[r, j] = sum_d weights[d + radius] * u_{t-1}[r, j + d], float64.
    ``rounded``: each step's result rounded to that operand precision
    of ``rounding.py`` (the control of ``checks/control_stencil.py``)."""
    U = np.array(U0, dtype=np.float64)
    n = U.shape[1]
    for _ in range(iterations):
        E = np.pad(U, ((0, 0), (radius, radius)))
        U = sum(weights[d] * E[:, d:d + n] for d in range(2 * radius + 1))
        if rounded is not None:
            U = round_operand(U, rounded).astype(np.float64)
    return U


def steps_on_vector(Y, iterations=ITERATIONS, radius=RADIUS,
                    weights=WEIGHTS):
    """B^iterations Y for the step matrix B of ``plain`` (U_t = U_{t-1}
    B: B[j + d, j] = weights[d + radius]), float64: (B y)[i] = sum_d
    weights[d + radius] * y[i - d], y zero outside."""
    Y = np.array(Y, dtype=np.float64)
    n = Y.shape[0]
    for _ in range(iterations):
        E = np.pad(Y, ((radius, radius), (0, 0)))
        Y = sum(weights[d] * E[2 * radius - d:2 * radius - d + n]
                for d in range(2 * radius + 1))
    return Y


#: rows converted to float64 at a time inside a row block: eight
#: threads then hold 0.3 GB of float64 at n = 40960 and not 2.7
STRIP = 128


def _times(M, X):
    """M X in float64, M a float32 matrix taken a strip of a row block
    at a time."""
    def block(r0, r1):
        return np.concatenate([M[r:min(r + STRIP, r1)].astype(np.float64) @ X
                               for r in range(r0, r1, STRIP)])
    return np.concatenate(over_rows(block, M.shape[0]))


def expected(U0, seed):
    """What a result is held against: (a) U_0 (B^I y) for three seeded
    y, which equals U_I y in exact arithmetic and weighs every entry of
    the result; (b) ``plain`` on ``ROWS`` seeded whole rows."""
    n = U0.shape[0]
    X = np.random.default_rng(seed + 1).standard_normal((n, 3))
    bands = np.linspace(0, n, min(ROWS, n) + 1).astype(int)
    rows = np.random.default_rng(seed + 2).integers(bands[:-1], bands[1:])
    return {"X": X, "want": _times(U0, steps_on_vector(X)),
            "rows": rows, "plain_rows": plain(U0[rows])}


def probe_number(U_out, exp):
    """max over the seeded y of ||U_out y - U_0 (B^I y)|| / ||U_0 (B^I
    y)||."""
    got = _times(U_out, exp["X"])
    return float((np.linalg.norm(got - exp["want"], axis=0)
                  / np.linalg.norm(exp["want"], axis=0)).max())


def rows_number(U_out, exp):
    """The largest relative error of a replayed row against ``plain``."""
    got = U_out[exp["rows"]].astype(np.float64)
    want = exp["plain_rows"]
    return float((np.linalg.norm(got - want, axis=1)
                  / np.linalg.norm(want, axis=1)).max())


def residual(U_out, exp):
    """The number compared: the larger of the two above, U_out the
    result pulled from the chip."""
    return max(probe_number(U_out, exp), rows_number(U_out, exp))
