"""Tile linear-algebra kernels: the BODY payloads of dense tile algorithms.

The reference delegates tile kernels to BLAS/LAPACK (DPLASMA sits on top of
the runtime; tests use hand-rolled GEMMs, e.g. dtd_test_simple_gemm.c).
Here each kernel is a jax-jit executable — XLA fuses scale/add into the
matmul and keeps the MXU fed; jit caches one executable per (shape, dtype)
so steady-state dispatch is a cache hit.

All kernels are functional (return new arrays) to match the device module's
stage-out convention; bf16 accumulation is avoided by pinning
``preferred_element_type`` to f32. Matmul *input* precision follows jax's
``jax_default_matmul_precision`` (TPU default: bf16-input MXU passes, ~2e-3
relative error on f32 tiles); set it to "highest" for LAPACK-grade f32
accuracy at ~3x the MXU cost.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular as _solve_tri

from . import pallas_kernels


@jax.jit
def potrf(t: Any) -> Any:
    """Cholesky of one diagonal tile: T = chol_L(T)."""
    return jnp.linalg.cholesky(t)


@jax.jit
def trsm_panel(t: Any, c: Any) -> Any:
    """Right-looking panel solve: C <- C * T^{-T} with T lower triangular
    (L[m,k] = A[m,k] L[k,k]^{-T})."""
    return _solve_tri(t, c.T, lower=True).T


@jax.jit
def syrk_ln(t: Any, a: Any) -> Any:
    """T <- T - A A^T (lower, no-transpose SYRK)."""
    return t - jnp.dot(a, a.T, preferred_element_type=jnp.float32)


@jax.jit
def gemm_nt(c: Any, a: Any, b: Any) -> Any:
    """C <- C - A B^T."""
    return c - jnp.dot(a, b.T, preferred_element_type=jnp.float32)


# The levels below ``highest`` of the mixed-precision tile Cholesky
# (ops/dpotrf_mp.py).  The kernels above follow the process's
# ``jax_default_matmul_precision``; these state their own: *mid* is f32
# operands at ``HIGH`` (bf16_3x, three MXU passes), *lo* is bf16
# operands in one pass.  Accumulation and the tile written are f32.
_MID = jax.lax.Precision.HIGH
#: a triangular solve is split down to diagonal blocks of this order
TRSM_LEAF = 256


@jax.jit
def gemm_nt_mid(c: Any, a: Any, b: Any) -> Any:
    """C <- C - A B^T, f32 operands in three bf16 passes."""
    return c - jnp.dot(a, b.T, precision=_MID,
                       preferred_element_type=jnp.float32)


@jax.jit
def gemm_nt_lo(c: Any, a: Any, b: Any) -> Any:
    """C <- C - A B^T with A and B as bf16 (the reshape engine's copies
    of the f32 tiles: the conversion is on the flow, not here), one MXU
    pass, accumulated and subtracted in f32."""
    return c - jnp.dot(a, b.T, precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=jnp.float32)


def trsm_panel_split(t: Any, c: Any, precision: Any, leaf: int) -> Any:
    """C <- C * T^{-T} by halving T: X1 = C1 T11^{-T}, X2 = (C2 - X1
    T21^T) T22^{-T}, the products at ``precision`` and the diagonal
    blocks of order ``leaf`` or less by ``trsm_panel``'s solve (XLA's
    triangular solve runs at ``highest`` whatever is asked: it holds
    1 / (NB / leaf) of the operations)."""
    n = t.shape[0]
    if n <= leaf or n % 2:
        return _solve_tri(t, c.T, lower=True).T
    h = n // 2
    x1 = trsm_panel_split(t[:h, :h], c[:, :h], precision, leaf)
    c2 = c[:, h:] - jnp.dot(x1, t[h:, :h].T, precision=precision,
                            preferred_element_type=jnp.float32)
    x2 = trsm_panel_split(t[h:, h:], c2, precision, leaf)
    return jnp.concatenate([x1, x2], axis=1)


@jax.jit
def trsm_panel_mid(t: Any, c: Any) -> Any:
    """``trsm_panel`` with its products in three bf16 passes."""
    return trsm_panel_split(t, c, _MID, TRSM_LEAF)


@jax.jit
def gemm_nn(c: Any, a: Any, b: Any) -> Any:
    """C <- C + A B."""
    return c + jnp.dot(a, b, preferred_element_type=jnp.float32)


@jax.jit
def gemm_nn_sub(c: Any, a: Any, b: Any) -> Any:
    """C <- C - A B (trailing update of LU)."""
    return c - jnp.dot(a, b, preferred_element_type=jnp.float32)


@jax.jit
def gemm(c: Any, a: Any, b: Any, alpha: float = 1.0, beta: float = 1.0) -> Any:
    """C <- beta*C + alpha*A@B (general tile GEMM). alpha/beta are traced
    scalars: one cached executable serves every scaling."""
    return beta * c + alpha * jnp.dot(a, b, preferred_element_type=jnp.float32)


@jax.jit
def geqrt(a: Any) -> Any:
    """QR of one diagonal tile: returns (R, Q) with A = Q R.

    The reference's GEQRT produces Householder vectors V in the lower part
    plus a block-reflector T; on TPU the compact-WY form would serialize
    into nb small reflector applications, so the explicit orthogonal factor
    Q (one extra nb x nb matmul per consumer, MXU-friendly) plays the role
    of (V, T)."""
    q, r = jnp.linalg.qr(a, mode="complete")
    return r, q


@jax.jit
def geqrt_r(a: Any) -> Any:
    """Last-panel geqrt: no Q consumers exist, so skip forming the
    orthogonal factor (mode="r") and return a zero placeholder."""
    return jnp.linalg.qr(a, mode="r"), jnp.zeros_like(a)


@jax.jit
def unmqr(q: Any, c: Any) -> Any:
    """Apply Q^T from geqrt to a tile right of the diagonal: C <- Q^T C."""
    return jnp.dot(q.T, c, preferred_element_type=jnp.float32)


@jax.jit
def tsqrt(r: Any, a: Any) -> Any:
    """Triangle-on-top-of-square QR: factor [R; A] (R upper triangular).

    Returns (R', Z, Q2): the updated nb x nb triangle, the zeroed-out
    square block (tile (m,k) of the final R is zero), and the orthogonal
    factor Q2 of the stacked system for tsmqr consumers."""
    nb = r.shape[0]
    q2, rf = jnp.linalg.qr(jnp.concatenate([r, a], axis=0), mode="complete")
    return rf[:nb, :], jnp.zeros_like(a), q2


@jax.jit
def tsqrt_r(r: Any, a: Any) -> Any:
    """Last-panel tsqrt: R-only factorization of [R; A], zero Q2
    placeholder (no tsmqr consumers on the final panel)."""
    nb = r.shape[0]
    rf = jnp.linalg.qr(jnp.concatenate([r, a], axis=0), mode="r")
    n2 = r.shape[0] + a.shape[0]
    return rf[:nb, :], jnp.zeros_like(a), jnp.zeros((n2, n2), r.dtype)


@jax.jit
def tsmqr(q2: Any, a1: Any, a2: Any) -> Any:
    """Apply Q2^T from tsqrt to a stacked tile pair: [A1; A2] <- Q2^T [A1; A2]."""
    top = a1.shape[0]
    s = jnp.dot(q2.T, jnp.concatenate([a1, a2], axis=0),
                preferred_element_type=jnp.float32)
    return s[:top], s[top:]


@jax.jit
def getrf_nopiv(a: Any) -> Any:
    """LU without pivoting of one square diagonal tile (in-place storage:
    unit-lower L below the diagonal, U on and above).

    Full-shape masked rank-1 updates inside a fori_loop keep shapes static
    for XLA (no dynamic slicing). Each of the n steps does a full m x n
    outer-product update (masked lanes compute zeros), ~3x the flops of a
    true unblocked LU — the price of one cached executable with no
    dynamic shapes."""
    n = min(a.shape)
    rows = jnp.arange(a.shape[0])
    cols = jnp.arange(a.shape[1])

    def step(k, acc):
        col = acc[:, k]
        piv = acc[k, k]
        l = jnp.where(rows > k, col / piv, 0.0)
        row = jnp.where(cols > k, acc[k, :], 0.0)
        acc = acc - jnp.outer(l, row)
        return acc.at[:, k].set(jnp.where(rows > k, l, col))

    return jax.lax.fori_loop(0, n, step, a)


@jax.jit
def trsm_lower_unit(t: Any, c: Any) -> Any:
    """Row-panel update for LU: C <- L^{-1} C, L = unit-lower of T."""
    return _solve_tri(t, c, lower=True, unit_diagonal=True)


@jax.jit
def trsm_lower(t: Any, c: Any) -> Any:
    """C <- L^{-1} C, L = (non-unit) lower of T (forward substitution)."""
    return _solve_tri(t, c, lower=True)


@jax.jit
def trsm_lower_trans(t: Any, c: Any) -> Any:
    """C <- L^{-T} C, L = lower of T (backward substitution)."""
    return _solve_tri(t, c, lower=True, trans="T")


@jax.jit
def gemm_tn_sub(c: Any, a: Any, b: Any) -> Any:
    """C <- C - A^T B (backward-substitution update)."""
    return c - jnp.dot(a.T, b, preferred_element_type=jnp.float32)


@jax.jit
def gemm_tn(c: Any, a: Any, b: Any) -> Any:
    """C <- C + A^T B (the off-diagonal accumulation of L^T L)."""
    return c + jnp.dot(a.T, b, preferred_element_type=jnp.float32)


@jax.jit
def trsm_lower_right_neg(t: Any, c: Any) -> Any:
    """C <- -C L^{-1}, L = (non-unit) lower of T: the column step of the
    in-place triangular inverse (solved as L^T X^T = -C^T)."""
    return -_solve_tri(t, c.T, lower=True, trans="T").T


@jax.jit
def trtri_lower(t: Any) -> Any:
    """T <- L^{-1}, L = (non-unit) lower of T: the inverse of one
    diagonal tile, lower triangular (zeros above the diagonal)."""
    return _solve_tri(t, jnp.eye(t.shape[0], dtype=t.dtype), lower=True)


@jax.jit
def trmm_lower_trans(t: Any, c: Any) -> Any:
    """C <- L^T C, L = lower of T (what lies above T's diagonal is not
    read)."""
    return jnp.dot(jnp.tril(t).T, c, preferred_element_type=jnp.float32)


@jax.jit
def lauum_lower(t: Any) -> Any:
    """T <- L^T L, L = lower of T: the whole symmetric product, so the
    diagonal tile of an inverse is right in both triangles."""
    l = jnp.tril(t)
    return jnp.dot(l.T, l, preferred_element_type=jnp.float32)


@jax.jit
def syrk_lt(t: Any, a: Any) -> Any:
    """T <- T + A^T A (transposed SYRK; the whole symmetric product)."""
    return t + jnp.dot(a.T, a, preferred_element_type=jnp.float32)


@jax.jit
def trsm_upper_right(t: Any, c: Any) -> Any:
    """Column-panel update for LU: C <- C U^{-1}, U = upper of T
    (solved as U^T X^T = C^T)."""
    return _solve_tri(t, c.T, lower=False, trans="T").T


# --- LU with partial pivoting on block columns (ops/dgetrf_1d.py) ---------
#
# Every kernel below works on a WHOLE block column at one static shape,
# (N, nb): which rows are active is an operand, never a Python int, so
# one program serves every panel index.  What a kernel MOVES and
# multiplies is the active part: the panel's strip passes and the update
# walk blocks of rows from the panel's first row down and store the at
# most 2 nb rows the pivots moved; only LASWP gathers a whole column.
# The pivot tile that travels between them is int32, (4, N):
#   row 0: [first row of the NEXT panel, first row of THIS panel, 0...]
#   row 1: g, the panel's interchanges as a gather: after[i] = before[g[i]]
#   row 2: perm, every interchange so far: row i is original row perm[i]
#   row 3: ipiv so far, LAPACK's (0-based): row i was swapped with ipiv[i]
PIV_ROWS = 4


#: columns of a panel factored together, one column step after another
LU_STRIP = 32


def _lu_strip(st: Any, d0: Any) -> Any:
    """Partial pivoting on one strip of a panel, column after column.

    ``st`` is the strip TRANSPOSED, (w, N): ``st[i]`` is column i over
    all N rows, which lie along the array's minor (lane) dimension, so
    the pivot search is a reduction over lanes and the rank-1 update
    one elementwise pass.  Column i is active from row ``d0 + i`` down;
    the rows above are never read or written.  Each step: the pivot is
    the entry of largest magnitude among the active rows (the first on
    a tie), its row is exchanged with row ``d0 + i`` in all w columns,
    the multipliers replace the column under the diagonal and the
    strip's later columns are updated.  Returns (the strip, the
    interchanges as a gather over all N rows, the w pivot rows); the
    panel's pass reads that gather at the at most 2 w rows that moved
    (the pivot rows and the block row) and moves nothing else.

    This is the lowering for every platform but the TPU, and for the
    shapes the kernel does not take: the strip is the carry of an XLA
    loop, and XLA keeps a carry in HBM, so on the v5e each step reads
    and writes the whole strip in several operations (7 us a step at
    (32, 16384)).  On the TPU :func:`_lu_strip_lowered` runs the same
    steps as one Mosaic kernel over the strip held in VMEM
    (``pallas_kernels.lu_strip_vmem``, 1.4 us a step), with the same
    pivots and, wherever no inactive row holds a negative zero, the same
    bits."""
    w, n = st.shape
    lane = jnp.arange(n, dtype=jnp.int32)
    col = jnp.arange(w, dtype=jnp.int32)[:, None]

    def exchange(x, d, p, axis):
        xd = jax.lax.dynamic_slice_in_dim(x, d, 1, axis)
        xp = jax.lax.dynamic_slice_in_dim(x, p, 1, axis)
        x = jax.lax.dynamic_update_slice_in_dim(x, xp, d, axis)
        return jax.lax.dynamic_update_slice_in_dim(x, xd, p, axis), xd, xp

    def step(i, carry):
        st, g, piv = carry
        d = d0 + i
        row = jax.lax.dynamic_slice(st, (i, 0), (1, n))
        p = jnp.argmax(jnp.where(lane >= d, jnp.abs(row[0]), -1)
                       ).astype(jnp.int32)
        st, under_d, under_p = exchange(st, d, p, 1)
        g, _, _ = exchange(g, d, p, 0)
        # column i after the exchange, without reading the strip again
        row = jnp.where(lane == p, under_d[i], row)
        mult = jnp.where(lane > d, row / under_p[i], 0)
        st = jnp.where((col == i) & (lane > d), mult,
                       st - jnp.where(col > i, under_p, 0) * mult)
        return st, g, jax.lax.dynamic_update_slice(piv, p[None], (i,))

    return jax.lax.fori_loop(0, w, step,
                             (st, lane, jnp.zeros((w,), jnp.int32)))


def _lu_strip_lowered(st: Any, d0: Any) -> Any:
    """:func:`_lu_strip` in the lowering the program's platform and the
    strip's shape call for: on the TPU, for a strip the kernel takes
    (``pallas_kernels.lu_strip_fits``), the Mosaic kernel that keeps the
    strip in VMEM; the XLA loop anywhere else."""
    if not pallas_kernels.lu_strip_fits(*st.shape):
        return _lu_strip(st, d0)
    return jax.lax.platform_dependent(
        st, d0, tpu=pallas_kernels.lu_strip_vmem, default=_lu_strip)


def _lu_pass(x: Any, st: Any, rows: Any, new: Any, d0: Any, *, c0: int,
             c1: int) -> Any:
    """What strip ``[c0, c1)`` of a panel changes in it, in XLA.  The
    strip ``st`` (transposed, as the strip kernel left it) goes into its
    columns.  ``new`` holds the rows that moved on the columns from the
    strip's lane tile to the right edge, ``new[j]`` for row ``rows[j]``:
    the pivots' rows, then the block row, which right of the strip is U
    already.  They are stored left of the strip within its lane tile and
    right of it (as small scatters whose duplicates carry equal values;
    right of the strip the block row last, which a pivot row inside it
    must not outlive), and the rows under the block row take ``- L @ U``,
    L the strip; a row at or above the block row keeps its bits.
    Returns (the panel, the next strip's columns transposed, as the
    strip kernel takes them, or None after the panel's last strip).  The
    lowering for every platform but the TPU and for the shapes the
    kernel does not take (``pallas_kernels.lu_pass_vmem``)."""
    n, nb = x.shape
    w = c1 - c0
    lo = pallas_kernels.lu_pass_window(c0)
    x = x.at[:, c0:c1].set(st.T)
    if lo < c0:
        x = x.at[rows, lo:c0].set(new[:, :c0 - lo])
    if c1 == nb:
        return x, None
    right = new[:, c1 - lo:]
    x = x.at[rows[:w], c1:].set(right[:w])
    x = jax.lax.dynamic_update_slice(x, right[w:], (d0, c1))
    under = jnp.arange(n, dtype=jnp.int32) >= d0 + w
    below = jnp.where(under, st, 0).T
    x = x.at[:, c1:].set(jnp.where(
        under[:, None], gemm_nn_sub(x[:, c1:], below, right[w:]), x[:, c1:]))
    return x, x[:, c1:min(c1 + LU_STRIP, nb)].T


def _lu_pass_lowered(x: Any, st: Any, rows: Any, new: Any, d0: Any, *,
                     c0: int, c1: int) -> Any:
    """:func:`_lu_pass` in the lowering the program's platform and the
    panel's shape call for: on the TPU, for a panel the kernel takes
    (``pallas_kernels.lu_pass_fits``), the Mosaic kernel that walks the
    panel in place; XLA anywhere else."""
    if not pallas_kernels.lu_pass_fits(c1 - c0, *x.shape):
        return _lu_pass(x, st, rows, new, d0, c0=c0, c1=c1)
    return jax.lax.platform_dependent(
        x, st, rows, new, d0,
        tpu=functools.partial(pallas_kernels.lu_pass_vmem, c0=c0, c1=c1),
        default=functools.partial(_lu_pass, c0=c0, c1=c1))


def _lu_interchanged(g: Any, rows: Any, src: Any) -> Any:
    """``gs[g]`` for a strip's interchanges ``gs``, which are the
    identity but for ``gs[rows] = src`` (a row named twice has one
    source): the gather ``g`` taken after the strip's.  As 2 w compares
    of all of ``g`` and not as a gather of its N entries, which the TPU
    takes one entry at a time (0.116 ms at N = 16384, more than a whole
    pass of the panel; PERF.md section 5)."""
    hit = jnp.where(g[None, :] == rows[:, None], src[:, None], -1).max(axis=0)
    return jnp.where(hit >= 0, hit, g)


def _lu_panel(x: Any, r: Any) -> Any:
    """LU with exact partial pivoting of rows r.. of the (N, nb) block
    column ``x`` at its full static height, ``r`` an operand.
    Right-looking over strips of :data:`LU_STRIP` columns: a strip is
    factored over all the active rows (:func:`_lu_strip_lowered`: in
    VMEM by one Mosaic kernel where the program is lowered for the TPU
    and the strip's shape fits, by :func:`_lu_strip`'s XLA loop
    anywhere else) and then a strip PASS moves only what the strip
    changed (:func:`_lu_pass_lowered`: one Mosaic kernel over the panel
    in place on the TPU, XLA anywhere else):

    - the at most 2 w rows the strip's interchanges moved (its pivots'
      rows and its block row) are gathered, and the block row is solved
      against the strip's unit lower triangle;
    - the strip's own w columns go back into the panel;
    - the columns to its RIGHT are read once and written once: moved
      rows, block row and ``- L @ U`` under the block row in one walk,
      which also hands out the next strip; a row above the block row is
      never written;
    - the columns to its LEFT, already factored, are not touched, but
      for those of the strip's own lane tile (``LU_PASS_TILE`` = 128
      columns), which the walk brings in anyway and exchanges too.  The
      interchanges of the LATER lane tiles' strips are composed and
      applied to each lane tile ONCE, after the last strip: one gather a
      lane tile, one whole-panel pass a PANEL where there were four a
      strip.  Composing costs no gather of N row numbers either
      (:func:`_lu_interchanged`).

    ``lax.linalg.lu`` is still not used: on the TPU it is XLA's
    ``LuDecomposition``, which holds every row of a 128-column strip
    twice in 16 MiB of scoped VMEM and is refused at compile time from
    16384 rows on; the strip kernel asks for the VMEM its own (w, N)
    needs and compiles at N = 57344.  Returns (the column, the
    interchanges as a gather g: after[i] = before[g[i]], the nb pivot
    rows)."""
    n, nb = x.shape
    tile = pallas_kernels.LU_PASS_TILE
    piv = jnp.zeros((nb,), jnp.int32)
    moved = []
    strip = x[:, :LU_STRIP].T
    for c0 in range(0, nb, LU_STRIP):
        c1 = min(c0 + LU_STRIP, nb)
        w = c1 - c0
        d0 = r + c0
        lo = pallas_kernels.lu_pass_window(c0)
        st, gs, pv = _lu_strip_lowered(strip, d0)
        piv = piv.at[c0:c1].set(pv)
        rows = jnp.concatenate([pv, d0 + jnp.arange(w, dtype=jnp.int32)])
        src = gs[rows]
        moved.append((rows, src))
        if c1 == nb == w:           # one strip alone: nothing else to move
            x = st.T
            break
        new = jnp.take(x, src, axis=0, mode="clip")[:, lo:]
        if c1 < nb:
            u = trsm_lower_unit(
                jax.lax.dynamic_slice(st, (0, d0), (w, w)).T,
                new[w:, c1 - lo:])
            new = new.at[w:, c1 - lo:].set(u)
        x, strip = _lu_pass_lowered(x, st, rows, new, d0, c0=c0, c1=c1)
    # the interchanges of the later lane tiles' strips, on each lane tile
    g = jnp.arange(n, dtype=jnp.int32)
    cols = [x[:, (nb - 1) // tile * tile:]]
    for k in range(len(moved) - 1, -1, -1):
        g = _lu_interchanged(g, *moved[k])
        c0 = k * LU_STRIP
        if c0 and c0 % tile == 0:
            cols.append(jnp.take(x[:, c0 - tile:c0], g, axis=0,
                                 unique_indices=True, mode="clip"))
    return jnp.concatenate(cols[::-1], axis=1), g, piv


@jax.jit
def getrf_1d_panel(a: Any, q: Any) -> Any:
    """PANEL(k): LU with partial pivoting of the active rows of one
    block column.  ``a`` is (N, nb); ``q`` is the previous panel's pivot
    tile (for the first panel: zeros over the identity permutation),
    whose q[0, 0] is this panel's first row r.  Returns (the column:
    rows r.. factored, the rows above untouched; this panel's pivot
    tile)."""
    n, nb = a.shape
    r = q[0, 0]
    a, g, piv = _lu_panel(a, r)
    meta = jnp.zeros((n,), jnp.int32).at[0].set(r + nb).at[1].set(r)
    ipiv = jax.lax.dynamic_update_slice(q[3], piv, (r,))
    return a, jnp.stack([meta, g, q[2][g], ipiv])


def _lu_update(l: Any, c: Any, rows: Any, new: Any, r: Any) -> Any:
    """What panel k changes in one block column ``c`` right of it, in
    XLA.  ``new`` holds the 2 nb rows that moved, ``new[j]`` for row
    ``rows[j]``: the pivots' rows, then the block row ``r .. r + nb -
    1``, which is U already.  The pivots' rows are stored as one small
    scatter (a row named twice carries equal values), the rows under
    the block row take ``- L @ U`` (the rows of ``l`` at or above it
    masked out of the product, not sliced away), and the block row goes
    in last, which a pivot row inside it must not outlive.  The
    lowering for every platform but the TPU and for the shapes the
    kernel does not take (``pallas_kernels.lu_update_vmem``)."""
    n, nb = l.shape
    c = c.at[rows[:nb]].set(new[:nb])
    under = jnp.arange(n, dtype=jnp.int32)[:, None] >= r + nb
    c = gemm_nn_sub(c, jnp.where(under, l, 0), new[nb:])
    return jax.lax.dynamic_update_slice(c, new[nb:], (r, 0))


def _lu_update_lowered(l: Any, c: Any, rows: Any, new: Any, r: Any) -> Any:
    """:func:`_lu_update` in the lowering the program's platform and the
    column's shape call for: on the TPU, for a column the kernel takes
    (``pallas_kernels.lu_update_fits``), the Mosaic kernel that walks
    the column once; XLA anywhere else."""
    if not pallas_kernels.lu_update_fits(*l.shape, c.shape[1]):
        return _lu_update(l, c, rows, new, r)
    return jax.lax.platform_dependent(
        l, c, rows, new, r,
        tpu=pallas_kernels.lu_update_vmem, default=_lu_update)


@jax.jit
def getrf_1d_update(l: Any, p: Any, c: Any) -> Any:
    """One block column right of panel k: interchange its rows by the
    panel's pivots, solve the block row U_kn = L_kk^-1 C[r:r+nb] and
    update the rows below it, C -= L_*k U_kn.  ``l`` is the factored
    panel column, ``p`` its pivot tile (r = p[0, 1]).  Only what the
    panel changes is touched: the interchange moves the at most 2 nb
    rows the panel's pivots moved (its nb pivot rows ``p[3][r:r+nb]``
    and its block row, from the rows ``p[1]`` names: which rows move is
    known only now), not the column, and ONE walk
    (:func:`_lu_update_lowered`: a Mosaic kernel on the TPU, XLA
    anywhere else) stores them and subtracts the product over the rows
    under the block row, none over the rows above it; a row above ``r``
    keeps its bits."""
    nb = l.shape[1]
    r = p[0, 1]
    rows = jnp.concatenate([jax.lax.dynamic_slice(p[3], (r,), (nb,)),
                            r + jnp.arange(nb, dtype=jnp.int32)])
    new = jnp.take(c, p[1][rows], axis=0, mode="clip")
    u = trsm_lower_unit(jax.lax.dynamic_slice(l, (r, 0), (nb, nb)), new[nb:])
    return _lu_update_lowered(l, c, rows, new.at[nb:].set(u), r)


@jax.jit
def getrf_1d_laswp(a: Any, p: Any, f: Any) -> Any:
    """The interchanges of every LATER panel applied to a factored
    block column on the left: its rows are in the order p[2] (as its
    own panel left them) and go to the final order f[2]."""
    return jnp.take(a, jnp.argsort(p[2])[f[2]], axis=0,
                    unique_indices=True, mode="clip")


# The 1D stencil of ops/stencil_1d.py: every row of a tile is a piece
# of an independent 1D problem along the column index.  A ghost region
# is the ``radius`` columns of a neighbour that border the tile, handed
# over as a (radius, rows) array: row r of it is column r of the region
# (a column vector of f32 would occupy a whole lane tile a row on the
# chip, 128 times its bytes).
def _stencil_tile(x: Any, left: Any, right: Any, *, weights: tuple) -> Any:
    """One Jacobi step of a tile in XLA: the lowering for every
    platform but the TPU and for the shapes the kernel does not take
    (``pallas_kernels.stencil_tile_vmem``)."""
    r = len(weights) // 2
    nb = x.shape[1]
    e = jnp.concatenate([left.T, x, right.T], axis=1)
    out = weights[0] * e[:, 0:nb]
    for d in range(1, 2 * r + 1):
        out = out + weights[d] * e[:, d:d + nb]
    return out


@functools.partial(jax.jit, static_argnames=("weights",))
def stencil_tile(x: Any, left: Any = None, right: Any = None,
                 weights: tuple = (0.25, 0.5, 0.25)) -> Any:
    """One Jacobi step of a tile: out[:, j] = sum_d weights[d] *
    e[:, j + d] over e = [left | x | right], the tile between the
    ``len(weights) // 2`` columns of its two neighbours that border it
    (``stencil_ghosts``'s form; None: no neighbour, zeros).  Every
    product and sum in the tile's dtype, left to right.  On the TPU,
    for a tile the kernel takes (``pallas_kernels.stencil_fits``), ONE
    Mosaic kernel that reads the tile once and writes it once; XLA
    anywhere else."""
    r = len(weights) // 2
    rows = x.shape[0]
    zero = jnp.zeros((r, rows), x.dtype)
    left = zero if left is None else left
    right = zero if right is None else right
    xla = functools.partial(_stencil_tile, weights=weights)
    if not pallas_kernels.stencil_fits(rows, x.shape[1], r):
        return xla(x, left, right)
    return jax.lax.platform_dependent(
        x, left, right, default=xla,
        tpu=functools.partial(pallas_kernels.stencil_tile_vmem,
                              weights=weights))


@functools.partial(jax.jit, static_argnames=("radius",))
def stencil_ghosts(x: Any, radius: int = 1) -> Any:
    """The two ghost regions a tile hands to its neighbours: its first
    and its last ``radius`` columns, each as a (radius, rows) array."""
    return x[:, :radius].T, x[:, x.shape[1] - radius:].T


@jax.jit
def axpy(y: Any, x: Any, alpha: float = 1.0) -> Any:
    return y + alpha * x


@jax.jit
def scal(x: Any, alpha: float) -> Any:
    return alpha * x


@jax.jit
def transpose(x: Any) -> Any:
    return x.T
