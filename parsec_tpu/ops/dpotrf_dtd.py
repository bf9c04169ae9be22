"""Tile Cholesky (dpotrf_L) discovered at run time: the DTD driver.

The same factorization as ``ops/dpotrf.py``, written the way DPLASMA's
``tests/testing_dpotrf_dtd.c`` writes it: a sequential loop of
``insert_task`` calls over the tiles of A, from which the DTD front end
(``dsl/dtd``) discovers the DAG while it already runs.  The four task
classes, their kernels and the order of updates on every tile are
``ops.dpotrf``'s, so the factor is the same to the bit.
"""
from __future__ import annotations

import numpy as np

from ..collections.matrix import TiledMatrix
from ..dsl import dtd
from ..dsl.dtd import INOUT, INPUT, unpack_args
from .blocking import run_inserting


# Host incarnations: the same kernels, the result copied into the
# tile's host payload.  Like the device chores they are looked up in
# ``ops`` when they are needed, so a replaced kernel is what runs.

def _ops():
    from .. import ops
    return ops


def _potrf_host(es, task) -> None:
    (T,) = unpack_args(task)
    T[...] = np.asarray(_ops().potrf(T))


def _trsm_host(es, task) -> None:
    T, C = unpack_args(task)
    C[...] = np.asarray(_ops().trsm_panel(T, C))


def _syrk_host(es, task) -> None:
    T, A = unpack_args(task)
    T[...] = np.asarray(_ops().syrk_ln(T, A))


def _gemm_host(es, task) -> None:
    C, A, B = unpack_args(task)
    C[...] = np.asarray(_ops().gemm_nt(C, A, B))


#: (class name, tracked arguments, host body, device chore).  The chore
#: is the tile kernel of that name in ``ops`` itself, taking the
#: arguments in the order they are inserted: a module-level function
#: and so one identity per process, which makes every taskpool's stacked
#: programs the ones the first built (DeviceBatchSpec.cache_token).
_CLASSES = (("POTRF", 1, _potrf_host, "potrf"),
            ("TRSM", 2, _trsm_host, "trsm_panel"),
            ("SYRK", 2, _syrk_host, "syrk_ln"),
            ("GEMM", 3, _gemm_host, "gemm_nt"))


def dpotrf_dtd(context, A: TiledMatrix) -> None:
    """Run the Cholesky factorization of the SPD tiled matrix A in place
    through the DTD front end (lower triangle holds L on return).
    Blocking: the taskpool is added and the context started, every task
    inserted, every tile flushed home, then the taskpool waited for."""
    assert A.mt == A.nt, "dpotrf_dtd needs a square tile grid"
    run_inserting(context, "dpotrf_dtd", dtd.taskpool_new("dpotrf_dtd"),
                  lambda tp: insert_dpotrf(tp, A))


def insert_dpotrf(tp: "dtd.DTDTaskpool", A: TiledMatrix) -> None:
    """The insert loop of ``testing_dpotrf_dtd.c`` (lower) on the
    enqueued taskpool ``tp``: classes and their chores first, then for
    each k POTRF(k), the TRSMs of panel k, and per trailing row m its
    SYRK and GEMMs.  The priorities are that driver's."""
    classes = []
    for name, nb_flows, body, kernel in _CLASSES:
        tc = tp.create_task_class(name, nb_flows, body)
        tp.add_chore(tc, "tpu", getattr(_ops(), kernel))
        classes.append(tc)
    potrf, trsm, syrk, gemm = classes
    NT = A.nt
    insert = tp.insert_task_with_task_class
    # one lookup per tile of the lower triangle, not one per argument
    T = [[tp.tile_of(A, (m, n)) for n in range(m + 1)] for m in range(NT)]
    for k in range(NT):
        insert(potrf, (T[k][k], INOUT), priority=(NT - k) ** 3)
        for m in range(k + 1, NT):
            insert(trsm, (T[k][k], INPUT), (T[m][k], INOUT),
                   priority=(NT - m) ** 3
                   + 3 * (2 * NT - k - m - 1) * (m - k))
        for m in range(k + 1, NT):
            insert(syrk, (T[m][m], INOUT), (T[m][k], INPUT),
                   priority=(NT - m) ** 3 + 3 * (m - k))
            for n in range(k + 1, m):
                insert(gemm, (T[m][n], INOUT), (T[m][k], INPUT),
                       (T[n][k], INPUT),
                       priority=(NT - n) ** 3
                       + 3 * (2 * NT - n - m - 3) * (n - m) + 6 * (n - k))
