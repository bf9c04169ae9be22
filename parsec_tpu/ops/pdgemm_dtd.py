"""Tile GEMM (C <- alpha A B + beta C) discovered at run time over
several accelerators: the DTD driver.

The product of ``ops/pdgemm.py``, written the way PaRSEC's own
multi-accelerator gate writes it (``tests/dsl/dtd/dtd_test_simple_gemm.c``,
the run that prints ``DTD_GEMM PxQxg``): every tile of the three
collections advised to a preferred device over a P x Q grid of the
node's accelerators, then a sequential loop of ``insert_task`` calls,
GEMM(m, n, k) with A(m, k) and B(k, n) ``INPUT`` and C(m, n) ``INOUT``,
C flushed home, the taskpool waited for.  The one task class runs
``ops.gemm`` itself with alpha and beta as ``VALUE`` arguments, k
ascending on every C tile, so C is the same to the bit as
``ops.pdgemm``'s.
"""
from __future__ import annotations

from typing import Any, List, Optional, Tuple

import numpy as np

from ..collections.matrix import TiledMatrix
from ..dsl import dtd
from ..dsl.dtd import AFFINITY, INOUT, INPUT, VALUE, unpack_args
from .blocking import run_inserting


def _ops():
    from .. import ops
    return ops


def _gemm_host(es, task) -> None:
    C, A, B, alpha, beta = unpack_args(task)
    C[...] = np.asarray(_ops().gemm(C, A, B, alpha, beta))


def device_grid(g: int) -> Tuple[int, int]:
    """P x Q with P * Q = g, as square as g allows (P <= Q)."""
    p = max(d for d in range(1, int(g ** 0.5) + 1) if g % d == 0)
    return p, g // p


def advice_grid(context) -> Optional[List[List[Any]]]:
    """The accelerators tiles are advised to: the context's, in index
    order, as a P x Q grid; tile (m, n) of any of the three collections
    goes to ``grid[m % P][n % Q]``.  None with fewer than two (nothing
    to decide)."""
    devs = [d for d in context.devices if d.device_type == "tpu"]
    if len(devs) < 2:
        return None
    P, Q = device_grid(len(devs))
    return [devs[p * Q:(p + 1) * Q] for p in range(P)]


def pdgemm_dtd(context, A: TiledMatrix, B: TiledMatrix, C: TiledMatrix,
               alpha: float = 1.0, beta: float = 1.0) -> None:
    """C <- alpha A B + beta C over tiled collections through the DTD
    front end.  Blocking: every tile advised, the taskpool added and the
    context started, every task inserted, every written tile flushed
    home, then the taskpool waited for."""
    if A.nt != B.mt or A.mt != C.mt or B.nt != C.nt:
        raise ValueError("pdgemm_dtd: tile grids do not agree "
                         f"(A {A.mt}x{A.nt}, B {B.mt}x{B.nt}, "
                         f"C {C.mt}x{C.nt})")
    grid = advice_grid(context)
    if grid is not None:
        P, Q = len(grid), len(grid[0])
        for M in (C, A, B):
            for (m, n) in M.tiles():
                grid[m % P][n % Q].data_advise(M.data_of(m, n),
                                               "preferred_device")
    run_inserting(context, "pdgemm_dtd", dtd.taskpool_new("pdgemm_dtd"),
                  lambda tp: insert_pdgemm(tp, A, B, C, alpha, beta))


def insert_pdgemm(tp: "dtd.DTDTaskpool", A: TiledMatrix, B: TiledMatrix,
                  C: TiledMatrix, alpha: float, beta: float) -> None:
    """The insert loop of ``dtd_test_simple_gemm.c`` on the enqueued
    taskpool ``tp``: the class and its chore first, then for every tile
    of C its chain over k, beta applied by the first.  The device chore
    is the tile kernel ``gemm`` of ``ops`` itself (looked up here, so a
    replaced kernel is what runs): a module-level function and so one
    identity per process, which makes every taskpool's stacked programs
    the ones the first built."""
    gemm = tp.create_task_class("GEMM", 3, _gemm_host)
    tp.add_chore(gemm, "tpu", _ops().gemm)
    KT = A.nt
    insert = tp.insert_task_with_task_class

    def tiles_of(M: TiledMatrix, operand: str) -> List[List["dtd.DTDTile"]]:
        # one lookup a tile, not one per argument; three collections of
        # one type share its default name, so the operand names the wire
        return [[tp.tile_of(M, (i, j), wire_name=f"{M.name}.{operand}")
                 for j in range(M.nt)] for i in range(M.mt)]

    TA, TB, TC = tiles_of(A, "A"), tiles_of(B, "B"), tiles_of(C, "C")
    alpha, beta = float(alpha), float(beta)
    for m in range(C.mt):
        for n in range(C.nt):
            for k in range(KT):
                insert(gemm, (TC[m][n], INOUT | AFFINITY),
                       (TA[m][k], INPUT), (TB[k][n], INPUT),
                       (alpha, VALUE), (beta if k == 0 else 1.0, VALUE),
                       priority=KT - k)
