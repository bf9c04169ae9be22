"""Reference for the three-precision band tile Cholesky (ExaGeoStat on
PaRSEC: Abdulah et al., IEEE TPDS 33(4), 2022): M = L L' where a tile
product runs at the level of the tile it writes, by that tile's
distance d = m - n from the diagonal, in tiles:

    d < band_high               hi   operands keep 24 bits (f32, six bf16 passes)
    band_high <= d < band_mid   mid  16 bits (bf16_3x, three passes)
    band_mid <= d               lo    8 bits (bf16 operands, one pass)

Accumulation and every tile written are float32.  The triangular solve
of tile (m, k) runs at the level of (m, k) but never below mid.

The number compared is BAND-WISE.  The backward error of a Cholesky
factor is local: R(m, n) = sum_k L(m, k) L(n, k)' - M(m, n) carries the
rounding of the products that wrote tile (m, n) and of no other.  Each
tile column n is probed with three seeded x; a tile's number is the
largest ||R(m, n) x|| / || |L(m, :)| |L(n, :)|' |x| || over them; a
level's number the largest over its tiles; ``residual`` returns the
largest of (level number / level limit), so the cell's ``check.limit``
is 1.  The whole-matrix ||L(L'x) - Mx|| / ||Mx|| of ``cholesky.residual``
is set by the lo band and would not move if the hi band slipped a level.

Plain numpy, independent of the program.  The harness hands ``expected``
the matrix and the seed alone, so the tile and the bands are read where
the harness read them: the command line's ``--workload`` (and
``--rehearse``); a caller that has them passes them.
"""
import json
import os
import sys

import numpy as np

from .blocks import over_rows

#: Matern theta = (variance, range, smoothness); smoothness 0.5 is the
#: exponential kernel exp(-r / range): no Bessel function
THETA = (1.0, 0.03, 0.5)
LEVELS = ("hi", "mid", "lo")
#: significant bits an operand keeps, per level
BITS = {"hi": 24, "mid": 16, "lo": 8}
HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(os.path.dirname(HERE), "configs", "dpotrf-mp-1chip.json")


def _morton(ix, iy):
    """Interleave the bits of two uint32 grid coordinates."""
    def spread(v):
        v = v.astype(np.uint64)
        v = (v | (v << 16)) & 0x0000FFFF0000FFFF
        v = (v | (v << 8)) & 0x00FF00FF00FF00FF
        v = (v | (v << 4)) & 0x0F0F0F0F0F0F0F0F
        v = (v | (v << 2)) & 0x3333333333333333
        v = (v | (v << 1)) & 0x5555555555555555
        return v
    return spread(ix) | (spread(iy) << np.uint64(1))


def locations(n, seed):
    """n points in the unit square as ExaGeoStat generates them: the
    first n cells of a ceil(sqrt n) grid, each point moved from its
    cell's centre by a seeded uniform of up to 0.4 of the spacing,
    sorted by the Morton code of the cell."""
    s = int(np.ceil(np.sqrt(n)))
    rng = np.random.default_rng(seed)
    cell = np.arange(n)
    ix, iy = (cell % s).astype(np.uint32), (cell // s).astype(np.uint32)
    jitter = rng.uniform(-0.4, 0.4, size=(n, 2))
    xy = (np.stack([ix, iy], axis=1) + 0.5 + jitter) / s
    return xy[np.argsort(_morton(ix, iy), kind="stable")]


def make_input(n, seed):
    """The Matern covariance of ``locations(n, seed)`` at THETA: float32
    arithmetic throughout (the distances too), in place a row block to a
    thread; the diagonal is exactly the variance."""
    xy = locations(n, seed).astype(np.float32)
    x, y = np.ascontiguousarray(xy[:, 0]), np.ascontiguousarray(xy[:, 1])
    var, scale = np.float32(THETA[0]), np.float32(-1.0 / THETA[1])
    M = np.empty((n, n), dtype=np.float32)

    def rows(r0, r1):
        out = M[r0:r1]
        np.subtract(x[r0:r1, None], x[None, :], out=out)
        np.multiply(out, out, out=out)
        dy = y[r0:r1, None] - y[None, :]
        np.multiply(dy, dy, out=dy)
        np.add(out, dy, out=out)
        np.sqrt(out, out=out)
        np.multiply(out, scale, out=out)
        np.exp(out, out=out)
        if var != 1:
            np.multiply(out, var, out=out)

    over_rows(rows, n)
    return M


def level_of(d, band_high, band_mid):
    return "hi" if d < band_high else "mid" if d < band_mid else "lo"


def _cell_shape():
    """(nb, band_high, band_mid) of the cell the command line names."""
    from perfbench import spec
    from perfbench.class_roofline import _option
    name = _option("--workload")
    if not name:
        raise ValueError("cholesky_mp: no nb and bands given and no "
                         "--workload on the command line to read them from")
    cell = spec.Cell(spec.load_benchmark(), name)
    small = _option("--rehearse")
    nb = int(small.split(",")[1]) if small else cell.sizes["NB"]
    return nb, int(cell.args["band_high"]), int(cell.args["band_mid"])


def limits():
    """{level: limit} of the configuration's file."""
    with open(CONFIG) as f:
        return {k: float(v) for k, v in
                json.load(f)["check"]["level_limits"].items()}


def expected(M, seed, nb=None, band_high=None, band_mid=None):
    """The probes and what M makes of them: x_n on tile column n (three
    seeded columns each) and M(m, n) x_n for every lower tile."""
    if nb is None:
        nb, band_high, band_mid = _cell_shape()
    n = M.shape[0]
    nt = n // nb
    X = np.random.default_rng(seed + 1).standard_normal((nt, nb, 3))

    def rows(r0, r1):               # (r1 - r0, nt, 3): M(rows, n) x_n
        Mb = M[r0:r1].astype(np.float64).reshape(r1 - r0, nt, nb)
        return np.einsum("rnc,ncp->rnp", Mb, X)

    MX = np.concatenate(over_rows(rows, n))
    return {"X": X, "MX": MX, "nb": nb, "band_high": band_high,
            "band_mid": band_mid}


def level_numbers(factor, exp):
    """{level: its number}: the largest over the level's tiles and the
    three probes of ||R(m, n) x_n|| / || |L(m, :)| |L(n, :)|' |x_n| ||."""
    X, MX, nb = exp["X"], exp["MX"], exp["nb"]
    nt = X.shape[0]
    n = nt * nb

    def lower_rows(m, absolute):    # rows of tile row m of L, float64
        Lb = factor[m * nb:(m + 1) * nb, :(m + 1) * nb].astype(np.float64)
        Lb[:, m * nb:] = np.tril(Lb[:, m * nb:])
        return np.abs(Lb) if absolute else Lb

    def probes(absolute):           # Y[:, n, :] = L(n, :)' x_n, zero-padded
        Xa = np.abs(X) if absolute else X

        def one(m):
            y = np.zeros((n, 3))
            y[:(m + 1) * nb] = lower_rows(m, absolute).T @ Xa[m]
            return y
        return np.stack(_over(one, nt), axis=1)

    Y, Ya = probes(False), probes(True)
    Y2, Ya2 = Y.reshape(n, nt * 3), Ya.reshape(n, nt * 3)

    def tile_row(m):                # (m + 1, 3) numbers of tiles (m, 0..m)
        w = (m + 1) * nb
        got = (lower_rows(m, False) @ Y2[:w]).reshape(nb, nt, 3)
        scale = (lower_rows(m, True) @ Ya2[:w]).reshape(nb, nt, 3)
        err = got - MX[m * nb:(m + 1) * nb]
        return (np.linalg.norm(err, axis=0)
                / np.linalg.norm(scale, axis=0))[:m + 1]

    out = {lv: 0.0 for lv in LEVELS}
    for m, ratios in enumerate(_over(tile_row, nt)):
        for col, r in enumerate(ratios):
            lv = level_of(m - col, exp["band_high"], exp["band_mid"])
            worst = float(r.max()) if np.isfinite(r).all() else float("nan")
            if out[lv] == out[lv] and not worst <= out[lv]:   # a NaN stays
                out[lv] = worst
    return out


def _over(fn, nt, threads=4):
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=threads) as pool:
        return list(pool.map(fn, range(nt)))


def residual(factor, exp):
    """The largest of (level number / level limit); ``check.limit`` is
    1.  The three are printed, each beside its limit."""
    numbers, lim = level_numbers(factor, exp), limits()
    print("check levels: " + ", ".join(
        f"{lv} {numbers[lv]:.6e} (limit {lim[lv]:g}, "
        f"{numbers[lv] / lim[lv]:.4f} of it)" for lv in LEVELS),
        file=sys.stderr, flush=True)
    shares = [numbers[lv] / lim[lv] for lv in LEVELS]
    return float("nan") if any(s != s for s in shares) else max(shares)


def plain_factor(M, nb, band_high, band_mid, bits=None, lo_product_bits=24):
    """Right-looking tile Cholesky, one tile operation at a time, the
    operands of every tile product rounded to the level of the tile
    written (``bits``: {level: bits kept}, default BITS; a control gives
    one level fewer).  ``lo_product_bits`` under 24 also rounds each lo
    product before it is subtracted: the control "accumulated in
    bf16"."""
    bits = dict(BITS, **(bits or {}))
    A = np.array(M, dtype=np.float32)
    nt = A.shape[0] // nb

    def t(i, j):
        return A[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]

    def product(a, b, level):
        out = _round_bits(a, bits[level]) @ _round_bits(b, bits[level])
        if level == "lo" and lo_product_bits < 24:
            out = _round_bits(out, lo_product_bits)
        return out

    for k in range(nt):
        t(k, k)[:] = np.linalg.cholesky(t(k, k))
        # the chip solves a triangular system by inverting diagonal
        # blocks and multiplying, so the operand rounding applies
        inv_t = np.linalg.inv(
            np.tril(t(k, k)).astype(np.float64)).T.astype(np.float32)
        for m in range(k + 1, nt):
            solve = level_of(m - k, band_high, band_mid)
            t(m, k)[:] = product(t(m, k), inv_t,
                                 "mid" if solve == "lo" else solve)
        for m in range(k + 1, nt):
            t(m, m)[:] -= product(t(m, k), t(m, k).T, "hi")
            for n in range(k + 1, m):
                t(m, n)[:] -= product(
                    t(m, k), t(n, k).T, level_of(m - n, band_high, band_mid))
    return A


def _round_bits(x, bits):
    """float32 ``x`` with ``bits`` significant bits kept, to nearest even
    (``rounding.round_operand`` for any count of bits)."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    if bits >= 24:
        return x
    drop = 24 - bits
    u = x.view(np.uint32)
    bias = ((u >> drop) & 1) + ((1 << (drop - 1)) - 1)
    return ((u + bias.astype(np.uint32)) >> drop << drop).view(np.float32)
