"""``ops.dpotrf_dtd``: the tile Cholesky discovered at run time, and what
it forced in the DTD front end: classes made ahead of their tasks, a
window that holds, insert / window / flush on the phase clock.  Counts
and structure only: no time is asserted.
"""
import os
import sys
import threading
import time

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import dtd, ops
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.dsl.dtd import INOUT, unpack_args
from parsec_tpu.obs import phases
from parsec_tpu.ops.dpotrf_dtd import insert_dpotrf
from parsec_tpu.utils.params import params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.reference import cholesky  # noqa: E402

NB = 16
#: the limit of perfbench/configs/dpotrf-dtd-f32-1chip.json
LIMIT = 1.5e-6


def _n_tasks(nt):
    return nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6


def _n_flushes(nt):
    return nt * (nt + 1) // 2


def _tiled(M, nb=NB):
    n = M.shape[0]
    return TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)


def _accel(ctx):
    return [d for d in ctx.devices if d.device_type == "tpu"]


def _stat(ctx, key):
    return sum(d.stats[key] for d in _accel(ctx))


@pytest.fixture(scope="module")
def ctx():
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=4)
    yield c
    c.fini()


@pytest.fixture
def small_window():
    with params.cmdline_override("dtd_window_size", "64"), \
            params.cmdline_override("dtd_threshold_size", "32"):
        yield


def _in_thread(fn, timeout=120):
    """Run ``fn`` on a thread of its own and give it ``timeout`` seconds:
    a hang fails the test instead of the run."""
    box = {}

    def run():
        try:
            box["value"] = fn()
        except BaseException as exc:    # handed to the test's thread
            box["raised"] = exc

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), f"still running after {timeout} s"
    if "raised" in box:
        raise box["raised"]
    return box.get("value")


# --------------------------------------------------------------------- #
# the factor                                                            #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", [3, 1 << 20, (1 << 31) + 5])
def test_factor_against_the_plain_reference(ctx, seed):
    nt = 6
    M = cholesky.make_input(nt * NB, seed)
    A = _tiled(M)
    ops.dpotrf_dtd(ctx, A)
    factor = A.to_numpy()
    want = cholesky.plain_factor(M, NB)
    assert np.abs(np.tril(factor) - np.tril(want)).max() \
        <= 64 * np.finfo(np.float32).eps * np.abs(want).max()
    assert cholesky.residual(factor, cholesky.expected(M, seed)) <= LIMIT


@pytest.mark.parametrize("nt", [1, 2, 5, 8])
def test_bit_identical_to_ops_dpotrf(ctx, nt):
    """The same kernels in the same order on every tile."""
    M = cholesky.make_input(nt * NB, 11)
    A, B = _tiled(M), _tiled(M)
    ops.dpotrf(ctx, A)
    ops.dpotrf_dtd(ctx, B)
    assert np.array_equal(np.tril(A.to_numpy()), np.tril(B.to_numpy()))


@pytest.mark.parametrize("call", range(20))
def test_every_task_runs_on_the_accelerator(ctx, call):
    """A class has its chore before its first task: no task of a fresh
    taskpool can reach a worker without it (the boot race of a
    body-first class)."""
    nt = 4
    A = _tiled(cholesky.make_input(nt * NB, call))
    before = _stat(ctx, "tasks")
    ops.dpotrf_dtd(ctx, A)
    assert _stat(ctx, "tasks") - before == _n_tasks(nt)
    assert _stat(ctx, "batch_downgrades") == 0


def test_second_call_reuses_every_program():
    """Module-level chores: one identity per process, so a second
    taskpool builds no stacked program.  One worker, so that both calls
    dispatch the same buckets."""
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=1)
    try:
        M = cholesky.make_input(8 * NB, 5)
        ops.dpotrf_dtd(c, _tiled(M))
        before = {k: _stat(c, k)
                  for k in ("batches", "program_reuse", "first_calls")}
        ops.dpotrf_dtd(c, _tiled(M))
        batches = _stat(c, "batches") - before["batches"]
        assert batches > 0
        assert _stat(c, "program_reuse") - before["program_reuse"] == batches
        assert _stat(c, "first_calls") == before["first_calls"]
    finally:
        c.fini()


def test_host_bodies_give_the_same_factor():
    """With no accelerator attached the classes' host bodies run."""
    M = cholesky.make_input(4 * NB, 9)
    c = parsec_tpu.Context(nb_cores=2, enable_tpu=False)
    try:
        A = _tiled(M)
        ops.dpotrf_dtd(c, A)
        got = A.to_numpy()
    finally:
        c.fini()
    assert cholesky.residual(got, cholesky.expected(M, 9)) <= LIMIT


# --------------------------------------------------------------------- #
# classes before tasks                                                  #
# --------------------------------------------------------------------- #
def _bump(es, task):
    (x,) = unpack_args(task)
    x += 1


def test_two_classes_may_share_a_body(ctx):
    tp = dtd.taskpool_new()
    ctx.add_taskpool(tp)
    a = tp.create_task_class("A", 1, _bump)
    b = tp.create_task_class("B", 1, _bump)
    assert a is not b and [a.name, b.name] == ["A", "B"]
    tile = tp.tile_of_array(np.zeros((2, 2), np.float32))
    ta = tp.insert_task_with_task_class(a, (tile, INOUT))
    tb = tp.insert_task_with_task_class(b, (tile, INOUT), priority=7)
    assert ta.task_class is a and tb.task_class is b and tb.priority == 7
    tp.data_flush_all()
    tp.wait()
    assert np.array_equal(tile.data.sync_to_host().payload,
                          np.full((2, 2), 2, np.float32))


def test_body_first_insert_still_works(ctx):
    tp = dtd.taskpool_new()
    ctx.add_taskpool(tp)
    tile = tp.tile_of_array(np.zeros((2, 2), np.float32))
    tp.insert_task(_bump, (tile, INOUT))
    tp.add_chore(_bump, "tpu", lambda x: x + 1)
    tp.insert_task(_bump, (tile, INOUT))
    tp.data_flush_all()
    tp.wait()
    assert np.array_equal(tile.data.sync_to_host().payload,
                          np.full((2, 2), 2, np.float32))


def test_a_class_is_its_taskpools_and_keeps_its_flow_count(ctx):
    tp, other = dtd.taskpool_new(), dtd.taskpool_new()
    ctx.add_taskpool(tp)
    ctx.add_taskpool(other)
    try:
        tc = tp.create_task_class("A", 1, _bump)
        tile = tp.tile_of_array(np.zeros((2, 2), np.float32))
        with pytest.raises(AssertionError, match="another taskpool"):
            other.insert_task_with_task_class(tc, (tile, INOUT))
        with pytest.raises(AssertionError, match="2 tracked arguments"):
            tp.insert_task_with_task_class(tc, (tile, INOUT), (tile, INOUT))
        with pytest.raises(AssertionError, match="create_task_class"):
            tp.add_chore(lambda es, task: None, "tpu", lambda x: x)
    finally:
        tp.wait()
        other.wait()


def test_a_chore_alone_runs_under_its_class_name():
    """A task dispatched alone calls the kernel's clone named for the
    class (``jit_POTRF``), as a PTG body's does; a chore that is no
    plainly jitted kernel passes through."""
    from parsec_tpu.devices import batching
    clone = batching.kernel_named_for("POTRF", ops.potrf)
    assert clone is not ops.potrf and clone.__name__ == "POTRF"
    assert batching.kernel_named_for("POTRF", ops.potrf) is clone
    assert batching.KernelsNamedFor(ops, "POTRF").potrf is clone

    def plain(x):
        return x

    assert batching.kernel_named_for("POTRF", plain) is plain


# --------------------------------------------------------------------- #
# the window                                                            #
# --------------------------------------------------------------------- #
def test_the_window_holds(ctx, small_window):
    """Over the window the inserter is held until the threshold: the
    pool never holds more than window + 1 tasks, so all but that many
    had completed when the last insert returned."""
    nt = 8
    A = _tiled(cholesky.make_input(nt * NB, 2))
    tp = dtd.taskpool_new()
    assert (tp.window_size, tp.threshold_size) == (64, 32)
    seen = []
    inner = tp._insert

    def watched(*args):
        task = inner(*args)
        seen.append(tp._outstanding)
        return task

    tp._insert = watched
    ctx.add_taskpool(tp)
    ctx.start()
    insert_dpotrf(tp, A)
    completed = tp._inserted - tp._outstanding
    assert len(seen) == tp._inserted == _n_tasks(nt)
    assert max(seen) <= 65
    assert completed >= _n_tasks(nt) - 65
    tp.data_flush_all()
    _in_thread(tp.wait)
    assert tp.completed and tp._outstanding == 0 and tp._in_flight == 0
    ctx.wait()
    M = cholesky.make_input(nt * NB, 2)
    assert cholesky.residual(A.to_numpy(), cholesky.expected(M, 2)) <= LIMIT


def test_the_window_lets_go_when_nothing_is_in_flight(ctx, small_window):
    """Tasks that wait for something still to come (a message, a later
    insert) are outstanding but not in flight: the inserter goes on."""
    tp = dtd.taskpool_new()
    ctx.add_taskpool(tp)
    ran = []

    def body(es, task):
        ran.append(task)

    held = [tp._insert_local(body, [], [], None, 0, hold_deps=1)
            for _ in range(70)]
    assert tp._outstanding == 70 > tp.window_size and tp._in_flight == 0
    _in_thread(lambda: tp.insert_task(body), timeout=30)
    for task in held:       # what they waited for arrives
        if task.dtd.dep_satisfied():
            tp._schedule_new(task)
    _in_thread(tp.wait)
    assert len(ran) == 71 and tp._outstanding == 0 and tp._in_flight == 0


def test_an_error_while_the_inserter_is_held_surfaces():
    """A body raises while the window holds the inserting thread: the
    inserts return and ``wait`` raises, nothing hangs."""
    c = parsec_tpu.init(nb_cores=3)
    try:
        tp = dtd.taskpool_new()
        tp.window_size, tp.threshold_size = 8, 4
        c.add_taskpool(tp)
        c.start()
        tile = tp.tile_of_array(np.zeros(1, np.float32))

        def body(es, task):
            (x, k) = unpack_args(task)
            time.sleep(0.002)
            if k == 20:
                raise ValueError("body 20")

        def drive():
            for k in range(200):
                tp.insert_task(body, (tile, INOUT), k)
            tp.wait()

        with pytest.raises(RuntimeError, match="task body failed") as exc:
            _in_thread(drive, timeout=60)
        assert isinstance(exc.value.__cause__, ValueError)
        c.clear_task_errors()
    finally:
        c.fini()


# --------------------------------------------------------------------- #
# the phase clock                                                       #
# --------------------------------------------------------------------- #
def test_insert_window_and_flush_are_on_the_phase_clock(small_window):
    nt = 8
    assert {"dtd_insert", "dtd_window", "dtd_flush"} <= set(phases.PHASES)
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.Context(nb_cores=3, profile=True)
    try:
        phases.clear_completed()
        ops.dpotrf_dtd(c, _tiled(cholesky.make_input(nt * NB, 4)))
        (rec,) = phases.completed()
    finally:
        c.fini()
    assert rec["op"] == "dpotrf_dtd"
    assert set(rec["phases"]) <= set(phases.PHASES)
    got = rec["phases"]
    # every insert_task is a span: the DAG's tasks and the flushes
    assert got["dtd_insert"]["count"] == _n_tasks(nt) + _n_flushes(nt)
    assert got["dtd_flush"]["count"] == _n_flushes(nt)
    assert got["dtd_window"]["count"] >= 1
    mine = rec["by_thread"][rec["caller_thread"]]["phases"]
    assert mine["dtd_insert"]["count"] == got["dtd_insert"]["count"]
    assert mine["dtd_window"]["count"] == got["dtd_window"]["count"]
    for t in rec["by_thread"].values():
        assert sum(p["self_ns"] for p in t["phases"].values()) \
            + t["other_ns"] == rec["t1_ns"] - rec["t0_ns"]


def test_no_clock_no_span(ctx):
    """With nobody to read it the DTD sites stay on their fast path:
    the call's record has no phase table."""
    phases.clear_completed()
    ops.dpotrf_dtd(ctx, _tiled(cholesky.make_input(2 * NB, 1)))
    rec, = phases.completed()
    assert "phases" not in rec and rec["traced"] is False
    assert ctx._phase_clock is None
    phases.clear_completed()


@pytest.mark.parametrize("enable_tpu", [True, False])
def test_a_replaced_kernel_is_what_runs(monkeypatch, enable_tpu):
    """Chores and host bodies look their kernel up in ``ops`` when the
    taskpool is built: with GEMM returning its tile unchanged the factor
    misses the limit, on the accelerator and on the host."""
    M = cholesky.make_input(4 * NB, 6)
    monkeypatch.setattr(ops, "gemm_nt", lambda c, a, b: c)
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.Context(nb_cores=2, enable_tpu=enable_tpu)
    try:
        A = _tiled(M)
        ops.dpotrf_dtd(c, A)
        got = A.to_numpy()
    finally:
        c.fini()
    assert not cholesky.residual(got, cholesky.expected(M, 6)) <= LIMIT
