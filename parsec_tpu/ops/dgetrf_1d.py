"""LU with partial pivoting on block columns (dgetrf_1d) as a PTG graph.

DPLASMA's ``dplasma_dgetrf_1d(parsec, A, IPIV)`` (``tests/
testing_dgetrf_1d.c``, ``P = 1``): right-looking LU with row partial
pivoting on a matrix distributed by block columns -- HPL's algorithm on a
``1 x Q`` grid -- with the panel factored by ONE task that owns the
whole block column.  For ``k = 0 .. NT-1``, ``r_k`` the first row of
panel ``k``:

- ``PANEL(k)``: ``P_k A[r_k:, c_k] = [L_kk; L_*k] U_kk``, the pivot of a
  column the entry of largest magnitude on or below the diagonal (exact
  partial pivoting: every ``|l_ij| <= 1``); yields the pivot tile.
- ``UPDATE(k, n)``, ``n > k``: the panel's interchanges applied to block
  column ``n`` (a gather of the at most 2 NB rows they moved, by the
  pivot tile inside the kernel: which rows move is known only when
  ``PANEL(k)`` has run, the DAG is static),
  ``U_kn = L_kk^-1 A[r_k : r_k + NB, c_n]``, ``A[r_k + NB :, c_n] -=
  L_*k U_kn``.
- ``LASWP(n)``, ``n < NT-1``: the interchanges of every later panel
  applied to the factored block column ``n`` after the sweep, in one
  gather from the order its own panel left to the final one, so that on
  return ``A`` holds the packed factors of ONE row permutation:
  ``P A = L U``.

What the layout is and why.  ``A`` is a
:class:`~parsec_tpu.collections.BlockColumnCyclic` (DPLASMA's own
``mb = M`` requirement): the collection's unit is the block column, so a
pivot search and a row interchange stay inside one task and one array
-- a JAX array cannot alias its neighbours the way upstream's pointer
and leading dimension do.  Every kernel runs at ONE static shape,
``(N, NB)``: the first active row reaches it as DATA, in the pivot tile
(``ops.getrf_1d_panel`` reads its ``r_k`` from the tile the previous
panel wrote, the updates and ``LASWP`` from their panel's tile), the
active rows are taken by ``dynamic_slice`` and a row mask.  No
body reads a task local, so the stacked programs of a class
(devices/batching.py) and the kernel a lone task runs are the same for
every ``k``: the programs held do not grow with ``NT``.  Where a kernel
still computes masked rows (the XLA lowerings) they are never counted
as work.

The pivot tile is int32, ``(4, N)`` (``ops.linalg``): a WRITE-only NEW
flow of ``PANEL(k)`` read by the ``NT-1-k`` updates, by ``PANEL(k+1)``
(the chain that carries the offset and the permutation so far) and by
``LASWP``; it lives on the chip beside the f32 columns and is never
pulled to the host inside the call.  ``IPIV``, the second descriptor,
has two tiles: the state before the first panel (staged in once) and the
last panel's tile.

Over several accelerators.  On a context with ``g > 1`` accelerators
the entry point lays the block columns out ``1 x g`` as HPL does on a
``1 x Q`` grid: column ``n`` is advised to accelerator ``n mod g`` in
index order (``data_advise(.., "preferred_device")``;
``layout_1xq``), so its first writer goes there by advice and every
later one because that chip owns it: ``PANEL(n)``, every
``UPDATE(k, n)`` and ``LASWP(n)`` run on one chip, and the columns with
the most updates behind them never share one.  The panel reaches the
other chips when their updates stage it in: each pulls the whole block
column from the chip that factored it, once (``dev.stats``:
``peer_pulls``, ``stage_in_peer_bytes``).  A column the caller advised
stays where the caller said; with one accelerator nothing is advised.
A chip then holds at most its ``ceil(NT / g)`` columns' ``UPDATE`` or
``LASWP`` tasks at once, and in what buckets they reach its manager is
the arrival's to say, call by call: the entry point tells the two
classes' batch specs that count (``ahead``), and each chip builds the
lone program and every stacked bucket up to it at its first task of the
class, in the process's first call, and none in a later one.
"""
from __future__ import annotations

from collections import Counter
from typing import Any

import numpy as np

from ..collections.matrix import TiledMatrix, TwoDimBlockCyclic
from ..dsl import ptg
from .blocking import run_blocking
from .linalg import PIV_ROWS

DGETRF_1D_JDF = """
descA [ type="collection" ]
descP [ type="collection" ]
NT [ type="int" ]

PANEL(k)

k = 0 .. NT-1

: descA( 0, k )

RW    A <- (k == 0) ? descA( 0, k ) : C UPDATE( k-1, k )
        -> L UPDATE( k, k+1 .. NT-1 )
        -> (k < NT-1) ? A LASWP( k ) : descA( 0, k )
READ  Q <- (k == 0) ? descP( 0, 0 ) : P PANEL( k-1 )
WRITE P -> (k < NT-1) ? Q PANEL( k+1 )  [shape="descP.tile_shape(0, 0)" dtype="int32"]
        -> P UPDATE( k, k+1 .. NT-1 )
        -> (k < NT-1) ? P LASWP( k )
        -> (k == NT-1) ? F LASWP( 0 .. NT-2 )
        -> (k == NT-1) ? descP( 0, 1 )

; (NT - k) * 1000 + 900

BODY [type=tpu]
{
    A, P = ops.getrf_1d_panel(A, Q)
}
END

UPDATE(k, n)

k = 0 .. NT-2
n = k+1 .. NT-1

: descA( 0, n )

READ L <- A PANEL( k )
READ P <- P PANEL( k )
RW   C <- (k == 0) ? descA( 0, n ) : C UPDATE( k-1, n )
       -> (n == k+1) ? A PANEL( k+1 ) : C UPDATE( k+1, n )

; (NT - k) * 1000 - 100 - (n - k)

BODY [type=tpu]
{
    C = ops.getrf_1d_update(L, P, C)
}
END

LASWP(n)

n = 0 .. NT-2

: descA( 0, n )

RW   A <- A PANEL( n )
       -> descA( 0, n )
READ P <- P PANEL( n )
READ F <- P PANEL( NT-1 )

; 0

BODY [type=tpu]
{
    A = ops.getrf_1d_laswp(A, P, F)
}
END
"""

_factory = None


def dgetrf_1d_factory() -> "ptg.JDFFactory":
    global _factory
    if _factory is None:
        _factory = ptg.compile_jdf(DGETRF_1D_JDF, name="dgetrf_1d")
    return _factory


def dgetrf_1d_ipiv(A: TiledMatrix) -> TwoDimBlockCyclic:
    """The second descriptor of ``dplasma_dgetrf_1d``: two pivot tiles,
    the state before the first panel (first row 0, the identity
    permutation, no pivot yet) and room for the last panel's."""
    ipiv = TwoDimBlockCyclic(PIV_ROWS, 2 * A.lm, PIV_ROWS, A.lm,
                             dtype=np.int32)
    start = np.zeros((PIV_ROWS, A.lm), np.int32)
    start[1] = start[2] = np.arange(A.lm, dtype=np.int32)
    ipiv.set_tile(0, 0, start)
    return ipiv


def dgetrf_1d_taskpool(A: TiledMatrix, IPIV: TiledMatrix):
    from .. import ops as ops_module
    if A.mt != 1 or A.lm < A.ln:
        raise ValueError(
            f"dgetrf_1d factors block columns (one tile row, mb = M, as "
            f"collections.BlockColumnCyclic tiles them) of a matrix at "
            f"least as tall as wide; got {A.lm}x{A.ln} in {A.mt}x{A.nt} "
            f"tiles of {A.mb}x{A.nb}")
    tp = dgetrf_1d_factory().new(descA=A, descP=IPIV, NT=A.nt)
    tp.global_env["ops"] = ops_module
    return tp


def layout_1xq(context, A: TiledMatrix) -> int:
    """Advise block column ``n`` of ``A`` to accelerator ``n mod g`` of
    the context's ``g`` accelerators, in index order; a column that
    already names an accelerator of the context keeps it.  Nothing with
    fewer than two accelerators (nothing to decide).  Returns the most
    columns one accelerator was given, 0 where nothing was decided."""
    devs = [d for d in context.devices if d.device_type == "tpu"]
    if len(devs) < 2:
        return 0
    indices = {d.device_index for d in devs}
    for n in range(A.nt):
        data = A.data_of(0, n)
        if data.preferred_device not in indices:
            devs[n % len(devs)].data_advise(data, "preferred_device")
    return max(Counter(A.data_of(0, n).preferred_device
                       for n in range(A.nt)).values())


def dgetrf_1d(context, A: TiledMatrix) -> Any:
    """Factor ``P A = L U`` in place with exact partial pivoting: on
    return ``A`` (block columns) holds unit-lower ``L`` strictly below
    the diagonal and ``U`` on and above, of ONE row permutation -- the
    block columns on the left are interchanged too.  Returns the pivots
    as LAPACK's ``ipiv`` (0-based, length ``min(M, N)``: row ``i`` was
    interchanged with row ``ipiv[i]``, in order), an array that stays
    where the last panel ran: nothing is pulled to the host here.  On
    several accelerators the block columns are laid out cyclically over
    them first (``layout_1xq``).  Blocking: enqueue + wait."""
    share = layout_1xq(context, A)
    IPIV = dgetrf_1d_ipiv(A)
    tp = dgetrf_1d_taskpool(A, IPIV)
    if share > 1:
        # a chip holds at most its columns' updates (or LASWPs) at once
        for tc in tp.task_classes:
            for chore in tc.incarnations:
                if tc.name != "PANEL" and chore.batch_spec is not None:
                    chore.batch_spec.ahead = share
    run_blocking(context, "dgetrf_1d", [tp])
    return IPIV.data_of(0, 1).newest_copy().payload[3, :min(A.lm, A.ln)]
