"""Chunked set stage-in (``JaxDevice._dispatch_ready``): a drained ready
set whose host tiles pass ``devices.tpu.STAGE_CHUNK_BYTES`` is staged a
chunk at a time, each chunk's tasks dispatched right behind its ONE
list ``device_put``, the tasks that wait for no host tile ahead of the
first; a set under the bound is one put and one grouping as before.
Where the manager's last finished wait says its chip had not made it
wait (``JaxDevice._note_wait``) the bound is ``STAGE_WHOLE_FACTOR``
times that.  Counts, orders and values only: no time is asserted.  The
bound is moved by monkeypatch, never by a parameter, and a reading is
what ``drain`` takes from the device's own counters.
"""
import contextlib

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import dtd, ops
from parsec_tpu.collections import BlockColumnCyclic, TwoDimBlockCyclic
from parsec_tpu.devices import tpu
from parsec_tpu.devices.tpu import JaxDevice
from parsec_tpu.dsl.dtd import INOUT, INPUT, unpack_args
from parsec_tpu.utils.params import params

NB = 16
TILE = NB * NB * 4
TASK = 3 * TILE     # a burst task brings three tiles of its own
BURST = 12
ABOVE_EVERY_SET = 1 << 62
KEYS = ("stage_in_tiles", "stage_in_transfers", "stage_in_bytes",
        "stage_chunks", "tasks_ahead_of_copy", "sets_whole_by_wait",
        "batches", "batched_tasks", "dispatch_tasks", "tasks",
        "set_stage_n", "group_n")
MS = 1_000_000
#: what a finished wait's brackets moved by (``_finish_wait``): the chip
#: made the manager wait a ninth of its work, exactly a tenth (not
#: UNDER it), an eleventh; and a cold wait: next to nothing by the
#: brackets, but all of ``dispatch`` was programs' first calls
CHIP_BOUND = {"chip_wait": 10 * MS, "dispatch": 50 * MS, "epilog": 40 * MS}
A_TENTH = {"chip_wait": 10 * MS, "group": 100 * MS}
MANAGER_BOUND = {"chip_wait": 10 * MS, "set_stage": 20 * MS,
                 "group": 20 * MS, "dispatch": 40 * MS, "epilog": 20 * MS,
                 "complete": 10 * MS}
COLD = {"chip_wait": 10 * MS, "dispatch": 60_000 * MS,
        "first_call": 60_000 * MS, "complete": 30 * MS}


def _context(nb_cores=1, **over):
    """ONE accelerator and, unless asked otherwise, one worker (the
    caller, inside ``wait``): a burst inserted before ``wait`` then
    reaches the device's queue whole."""
    over.setdefault("device_tpu_max", 1)
    with contextlib.ExitStack() as stack:
        for k, v in over.items():
            stack.enter_context(params.cmdline_override(k, str(v)))
        return parsec_tpu.init(nb_cores=nb_cores)


def _dev(ctx):
    dev, = (d for d in ctx.devices if d.device_type == "tpu")
    return dev


def _finish_wait(ctx, dev, moved, calls=1):
    """A ``wait()`` of ``ctx`` ends in which ``dev``'s manager retired
    ``calls`` calls and its brackets moved by ``moved`` (ns by bracket;
    ``first_call``: the part of ``dispatch`` in programs' first calls):
    the counters are the device's own, the reading ``drain`` takes."""
    for name, ns in moved.items():
        dev.stats[name + "_ns"] += ns
    dev.stats["retired_calls"] += calls
    dev.drain(ctx)


def _burst(ctx, n=BURST, nbs=()):
    """``n`` independent GEMM tasks of one class, each over three host
    tiles of its own (of order ``nbs[i]`` where given, else NB),
    inserted and NOT yet run: with ``device_batch_max = n`` the
    accelerator drains them as ONE ready set.  Returns the taskpool and
    the tiles."""
    import jax
    import jax.numpy as jnp
    tp = dtd.taskpool_new()
    ctx.add_taskpool(tp)

    def host(es, task):
        c, a, b = unpack_args(task)
        c -= a @ b.T

    tc = tp.create_task_class("GEMM", 3, host)
    tp.add_chore(tc, "tpu", jax.jit(
        lambda c, a, b: c - jnp.dot(a, b.T,
                                    preferred_element_type=jnp.float32)))
    rng = np.random.RandomState(5)
    nbs = list(nbs) + [NB] * (n - len(nbs))
    tiles = [[tp.tile_of_array(rng.rand(nb, nb).astype(np.float32))
              for _ in range(3)] for nb in nbs]
    for c, a, b in tiles:
        tp.insert_task_with_task_class(tc, (c, INOUT), (a, INPUT),
                                       (b, INPUT))
    return tp, tiles


def _assert_burst_result(tiles):
    rng = np.random.RandomState(5)
    for c, _a, _b in tiles:
        nb = c.data.get_copy(0).payload.shape[0]
        c0, a, b = (rng.rand(nb, nb).astype(np.float32) for _ in range(3))
        got = np.asarray(c.data.sync_to_host().payload)
        np.testing.assert_allclose(got, c0 - a @ b.T, rtol=1e-5, atol=1e-5)


@pytest.fixture
def events(monkeypatch):
    """The order of the set pass's puts and of the device calls filed:
    ``("put", tiles)`` and ``("call", tasks)``."""
    log = []
    put, filed = JaxDevice.prestage_many, JaxDevice._finish_submit

    def putting(self, datas, target=None):
        datas = list(datas)
        log.append(("put", len(datas)))
        return put(self, datas, target)

    def filing(self, es, rec):
        log.append(("call", len(rec.tasks)))
        return filed(self, es, rec)

    monkeypatch.setattr(JaxDevice, "prestage_many", putting)
    monkeypatch.setattr(JaxDevice, "_finish_submit", filing)
    return log


def _run_burst(prestaged=0, nbs=(), last_wait=None):
    """The burst through a fresh context; the first ``prestaged`` tasks'
    tiles are on the chip before anything is drained, and the
    accelerator's wait before this one read ``last_wait``.  Returns the
    accelerator's counters."""
    ctx = _context(device_batch_max=BURST)
    try:
        dev = _dev(ctx)
        if last_wait is not None:
            _finish_wait(ctx, dev, last_wait)
        tp, tiles = _burst(ctx, nbs=nbs)
        for task_tiles in tiles[:prestaged]:
            assert len(dev.prestage_many([t.data for t in task_tiles])) == 3
        before = {k: dev.stats[k] for k in KEYS}
        tp.wait()
        ctx.wait()
        _assert_burst_result(tiles)
        assert dev._backlog == [] and dev.device_load == 0.0
        return {k: dev.stats[k] - before[k] for k in KEYS}
    finally:
        ctx.fini()


def test_a_set_over_the_bound_goes_chunk_by_chunk(monkeypatch, events):
    """Twelve tasks of three tiles, the bound at two tasks' bytes: six
    puts of six tiles, each followed by ITS stacked call of two, so the
    first call is filed before the second put, let alone the last; every
    chunk but the last went out ahead of a copy of its own set."""
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", 2 * TASK)
    handed = []
    monkeypatch.setattr(tpu, "hand_over_kept", handed.append)
    d = _run_burst()
    assert events == [("put", 6), ("call", 2)] * 6
    # between two chunks the manager gives up the task kept for it
    assert len(handed) == 5
    assert d["stage_in_transfers"] == d["stage_chunks"] == 6
    assert d["stage_in_tiles"] == 3 * BURST
    assert d["stage_in_bytes"] == BURST * TASK
    assert d["batches"] == 6 and d["batched_tasks"] == d["tasks"] == BURST
    assert d["tasks_ahead_of_copy"] == BURST - 2
    # one pass a chunk, the two brackets booked once each a pass
    assert d["set_stage_n"] == d["group_n"] == 6


def test_a_chunk_closes_at_the_task_that_reaches_the_bound(
        monkeypatch, events):
    """The bound is on bytes still to bring and closes at a task
    boundary: a byte over two tasks' worth takes a third task in."""
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", 2 * TASK + 1)
    d = _run_burst()
    assert events == [("put", 9), ("call", 2), ("call", 1)] * 4
    assert d["stage_chunks"] == 4
    assert d["tasks_ahead_of_copy"] == BURST - 3


def test_a_set_under_the_bound_is_one_put_and_one_grouping(
        monkeypatch, events):
    """Under the bound (here: the module's own 128 MiB) nothing is split
    and nothing counts as ahead of a copy: one put of every tile, then
    the buckets the set gives whole (12 = 8 + 4)."""
    d = _run_burst()
    assert events == [("put", 3 * BURST), ("call", 8), ("call", 4)]
    assert d["stage_in_transfers"] == d["stage_chunks"] == 1
    assert d["tasks_ahead_of_copy"] == 0
    assert d["set_stage_n"] == d["group_n"] == 1
    # and so is a set exactly one byte under it
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", BURST * TASK + 1)
    del events[:]
    assert _run_burst() == d
    assert events == [("put", 3 * BURST), ("call", 8), ("call", 4)]


def test_resident_tasks_of_a_mixed_set_go_before_its_first_put(
        monkeypatch, events):
    """Four of the twelve tasks find their tiles on the chip: they are
    stacked and dispatched before the first byte of the set is copied,
    and the eight others follow chunk by chunk."""
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", 2 * TASK)
    d = _run_burst(prestaged=4)
    assert events[:4] == [("put", 3)] * 4        # the test's own
    assert events[4:] == [("call", 4)] + [("put", 6), ("call", 2)] * 4
    assert d["stage_chunks"] == d["stage_in_transfers"] == 4
    assert d["stage_in_tiles"] == 3 * 8
    # the four resident tasks and every chunk but the last
    assert d["tasks_ahead_of_copy"] == 4 + 6
    # the first pass looked, sent the resident tasks and one chunk
    assert d["set_stage_n"] == d["group_n"] == 4


def test_resident_tasks_of_a_set_under_the_bound_stay_in_its_grouping(
        events):
    """Under the bound a mixed set is NOT split: the resident tasks
    share the buckets of the others (one x8 and one x4 call, not four
    calls), as before chunks."""
    d = _run_burst(prestaged=4)
    assert events[4:] == [("put", 3 * 8), ("call", 8), ("call", 4)]
    assert d["tasks_ahead_of_copy"] == 0


def test_a_task_that_waits_for_little_goes_ahead_of_a_front_of_large_tiles(
        monkeypatch, events):
    """Ten tasks over tiles four times as large arrive first, two over
    the small tiles last: over the bound the backlog is kept least
    bytes first (arrival order among equals), so the two small tasks
    ride in the FIRST chunk, with the large task that closes it, and
    the other large ones follow one a chunk in the order they came."""
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", 4 * TASK)
    d = _run_burst(nbs=[2 * NB] * 10)
    assert events == [("put", 9), ("call", 2), ("call", 1)] \
        + [("put", 3), ("call", 1)] * 9
    assert d["stage_chunks"] == 10
    assert d["tasks_ahead_of_copy"] == BURST - 1


@pytest.mark.parametrize("failing", ["put", "dispatch"])
def test_a_failure_mid_set_leaves_the_rest_where_drain_finds_it(
        monkeypatch, failing):
    """The third chunk's put (or its dispatch) raises: the DAG aborts,
    and every task not dispatched is still in the manager's backlog or
    back in the queue, where ``drain`` credits its load."""
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", 2 * TASK)
    put, dispatch = JaxDevice.prestage_many, JaxDevice._dispatch_stacked
    calls = {"put": 0, "dispatch": 0}

    def putting(self, datas, target=None):
        calls["put"] += 1
        if failing == "put" and calls["put"] == 3:
            raise RuntimeError("no put")
        return put(self, datas, target)

    def dispatching(self, es, spec, static, shapes, donate, chunk):
        calls["dispatch"] += 1
        if failing == "dispatch" and calls["dispatch"] == 3:
            raise RuntimeError("no dispatch")
        return dispatch(self, es, spec, static, shapes, donate, chunk)

    monkeypatch.setattr(JaxDevice, "prestage_many", putting)
    monkeypatch.setattr(JaxDevice, "_dispatch_stacked", dispatching)
    ctx = _context(device_batch_max=BURST)
    try:
        dev = _dev(ctx)
        tp, _tiles = _burst(ctx)
        with pytest.raises(RuntimeError):
            tp.wait()
        assert dev.stats["tasks"] == 4
        left = len(dev._backlog) + len(dev.pending)
        # a failed put puts its chunk back at the head of the backlog;
        # the bucket whose call raised is lost with it, as before chunks
        assert left == (8 if failing == "put" else 6)
        assert len(dev._backlog) == left and dev.device_load >= left
        dev.drain(ctx)
        assert dev._backlog == [] and len(dev.pending) == 0
        if failing == "put":
            assert dev.device_load == 0.0
    finally:
        with contextlib.suppress(RuntimeError):
            ctx.fini()


# ---------------------------------------------------------------- #
# the bound is what the manager's last finished wait says          #
# ---------------------------------------------------------------- #
@pytest.mark.parametrize("last_wait", [None, CHIP_BOUND, A_TENTH, COLD],
                         ids=["no-wait-yet", "a-ninth", "a-tenth",
                              "cold-first-calls"])
def test_a_manager_its_chip_made_wait_cuts_at_the_bound(
        monkeypatch, events, last_wait):
    """No wait finished yet, the chip waited for a tenth of the work
    or more, or a wait whose ``dispatch`` bracket was all programs'
    first calls (``first_call_ns`` is left out of the work: a cold call
    does not read as manager-bound): the set is cut exactly as
    ``test_a_set_over_the_bound_goes_chunk_by_chunk`` says."""
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", 2 * TASK)
    d = _run_burst(last_wait=last_wait)
    assert events == [("put", 6), ("call", 2)] * 6
    assert d["stage_in_transfers"] == d["stage_chunks"] == 6
    assert d["tasks_ahead_of_copy"] == BURST - 2
    assert d["sets_whole_by_wait"] == 0
    assert d["set_stage_n"] == d["group_n"] == 6


def test_a_manager_that_never_waited_takes_a_set_between_the_bounds_whole(
        monkeypatch, events):
    """The chip waited for an eleventh of the work: twelve tasks' bytes,
    between two tasks' and sixteen, go in ONE put and ONE grouping, the
    path of a set under the bound count for count, and the counter
    says why."""
    under = _run_burst()
    del events[:]
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", 2 * TASK)
    d = _run_burst(last_wait=MANAGER_BOUND)
    assert events == [("put", 3 * BURST), ("call", 8), ("call", 4)]
    assert d["stage_in_transfers"] == d["stage_chunks"] == 1
    assert d["tasks_ahead_of_copy"] == 0
    assert d["set_stage_n"] == d["group_n"] == 1
    assert d.pop("sets_whole_by_wait") == 1
    assert under.pop("sets_whole_by_wait") == 0
    assert d == under


@pytest.mark.parametrize("last_wait, cut", [
    (MANAGER_BOUND, [("put", 9), ("call", 2), ("call", 1)] * 4),
    (CHIP_BOUND, [("put", 3), ("call", 1)] * BURST)],
    ids=["at-the-larger-bound", "at-the-bound"])
def test_a_set_over_the_larger_bound_is_cut_there(
        monkeypatch, events, last_wait, cut):
    """The bound at one tile: eight tiles is the larger one, and twelve
    tasks' 36 tiles pass both.  A manager that never waited closes a
    chunk at the task that reaches eight tiles (the third: four puts),
    one its chip made wait at every task (twelve)."""
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", TILE)
    assert tpu.STAGE_WHOLE_FACTOR * TILE < BURST * TASK
    d = _run_burst(last_wait=last_wait)
    assert events == cut
    puts = sum(1 for kind, _n in cut if kind == "put")
    assert d["stage_chunks"] == d["set_stage_n"] == puts
    assert d["tasks_ahead_of_copy"] == BURST - BURST // puts
    assert d["sets_whole_by_wait"] == 0
    assert d["stage_in_tiles"] == 3 * BURST


def test_a_finished_wait_leaves_what_the_brackets_moved_by():
    """A device's first wait decides at the module's bound and leaves
    the reading: ``chip_wait`` and the five working brackets less
    ``first_call_ns``, as the device's own counters have them."""
    ctx = _context(device_batch_max=BURST)
    try:
        dev = _dev(ctx)
        assert dev.wait_reading is None
        assert dev._stage_bound == tpu.STAGE_CHUNK_BYTES
        tp, tiles = _burst(ctx)
        tp.wait()
        ctx.wait()
        _assert_burst_result(tiles)
        st = dev.stats
        worked = sum(st[b + "_ns"] for b in (
            "set_stage", "group", "dispatch", "epilog", "complete"))
        assert st["retired_calls"] == 2 and st["first_call_ns"] > 0
        assert dev.wait_reading == (st["chip_wait_ns"],
                                    worked - st["first_call_ns"])
        assert dev._stage_bound in (
            tpu.STAGE_CHUNK_BYTES,
            tpu.STAGE_WHOLE_FACTOR * tpu.STAGE_CHUNK_BYTES)
    finally:
        ctx.fini()


def test_a_wait_that_retired_nothing_keeps_the_last_reading():
    """The reading is of the last wait that retired a call; what the
    brackets moved by in a wait without one (an empty ``wait()``, a
    taskpool of host tasks) is not carried into the next reading
    either: the deltas are since the previous ``drain``."""
    ctx = _context()
    try:
        dev = _dev(ctx)
        whole = tpu.STAGE_WHOLE_FACTOR * tpu.STAGE_CHUNK_BYTES
        _finish_wait(ctx, dev, MANAGER_BOUND)
        assert dev.wait_reading == (10 * MS, 110 * MS)
        assert dev._stage_bound == whole
        _finish_wait(ctx, dev, CHIP_BOUND, calls=0)
        ctx.wait()      # nothing to run: nothing retired
        assert dev.wait_reading == (10 * MS, 110 * MS)
        assert dev._stage_bound == whole
        _finish_wait(ctx, dev, A_TENTH)
        assert dev.wait_reading == (10 * MS, 100 * MS)
        assert dev._stage_bound == tpu.STAGE_CHUNK_BYTES
        _finish_wait(ctx, dev, COLD)
        assert dev.wait_reading == (10 * MS, 30 * MS)
        assert dev._stage_bound == tpu.STAGE_CHUNK_BYTES
        _finish_wait(ctx, dev, MANAGER_BOUND, calls=3)
        assert dev._stage_bound == whole
    finally:
        ctx.fini()


def test_two_devices_of_one_context_decide_apart(monkeypatch):
    """The reading is a device's own: of two accelerators in one
    context the one that never waited for its chip takes its twelve
    tasks' tiles in one put, the other cuts the same set in six."""
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", 2 * TASK)
    ctx = _context(device_batch_max=BURST, device_tpu_max=2)
    try:
        never, waited = (d for d in ctx.devices if d.device_type == "tpu")
        _finish_wait(ctx, never, MANAGER_BOUND)
        _finish_wait(ctx, waited, CHIP_BOUND)
        assert never._stage_bound == 8 * waited._stage_bound
        tp, tiles = _burst(ctx, 2 * BURST)
        for dev, mine in ((never, tiles[:BURST]), (waited, tiles[BURST:])):
            for c, _a, _b in mine:
                dev.data_advise(c.data, "preferred_device")
        before = [{k: d.stats[k] for k in KEYS} for d in (never, waited)]
        tp.wait()
        ctx.wait()
        _assert_burst_result(tiles)
        d0, d1 = ({k: d.stats[k] - b[k] for k in KEYS}
                  for d, b in zip((never, waited), before))
        assert d0["tasks"] == d1["tasks"] == BURST
        assert d0["stage_in_tiles"] == d1["stage_in_tiles"] == 3 * BURST
        assert (d0["stage_chunks"], d0["tasks_ahead_of_copy"],
                d0["sets_whole_by_wait"]) == (1, 0, 1)
        assert (d1["stage_chunks"], d1["tasks_ahead_of_copy"],
                d1["sets_whole_by_wait"]) == (6, BURST - 2, 0)
    finally:
        ctx.fini()


def test_the_task_kept_for_a_busy_manager_goes_to_the_scheduler():
    """``schedule_keep_best`` keeps the best released task for the
    releasing thread; a manager that stays in its loop hands it to the
    scheduler, where another worker finds it."""
    from parsec_tpu.runtime import scheduling
    ctx = _context()
    try:
        tp, _tiles = _burst(ctx, 1)
        es = ctx.execution_streams[0]
        task = ctx.scheduler.select(es)
        assert task is not None and es.next_task is None
        es.next_task = task
        scheduling.hand_over_kept(es)
        assert es.next_task is None
        assert ctx.scheduler.select(es) is task
        scheduling.hand_over_kept(es)       # nothing kept: nothing done
        assert ctx.scheduler.select(es) is None
        scheduling.schedule(es, [task])
        tp.wait()
        ctx.wait()
    finally:
        ctx.fini()


# ---------------------------------------------------------------- #
# the same bits however a set is cut                               #
# ---------------------------------------------------------------- #
def _spd(n, seed):
    rng = np.random.default_rng(seed)
    W = rng.standard_normal((n, 64)).astype(np.float32)
    return np.eye(n, dtype=np.float32) + W @ W.T


def _square(n, seed):
    return np.random.default_rng(seed).uniform(
        -0.5, 0.5, (n, n)).astype(np.float32)


def _tiled(M, nb):
    n = M.shape[0]
    return TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)


def _dpotrf(ctx, entry, nt, nb):
    A = _tiled(_spd(nt * nb, 44), nb)
    getattr(ops, entry)(ctx, A)
    return [np.tril(A.to_numpy())]


def _dgetrf_1d(ctx, nt, nb):
    M = _square(nt * nb, 45)
    A = BlockColumnCyclic(*M.shape, nb, nb, dtype=np.float32).from_numpy(M)
    ipiv = ops.dgetrf_1d(ctx, A)
    return [A.to_numpy(), np.asarray(ipiv)]


def _pdgemm(ctx, nt, nb):
    A, B, C = (_tiled(_square(nt * nb, 46 + i), nb) for i in range(3))
    ops.pdgemm(ctx, A, B, C, alpha=0.51, beta=-0.42)
    return [C.to_numpy()]


OPERATIONS = {
    "dpotrf": lambda ctx: _dpotrf(ctx, "dpotrf", 6, 32),
    "dpotrf_dtd": lambda ctx: _dpotrf(ctx, "dpotrf_dtd", 6, 32),
    "dgetrf_1d": lambda ctx: _dgetrf_1d(ctx, 6, 32),
    "pdgemm": lambda ctx: _pdgemm(ctx, 4, 32),
}


#: on the CPU backend a GEMM task of ``ops.pdgemm`` dispatched ALONE and
#: the same task inside a stacked program differ by a unit in the last
#: place (its scalars are operands there and constants here: PERF.md
#: section 4, PR 42, of the DTD twin), whatever cut the sets: two runs
#: of the pass before chunks differ by it too.  So the product is
#: compared with stacking off: what the chunks change is then the
#: staging and the order alone.
OVERRIDES = {"pdgemm": {"device_batch_max": 1}}


def _run(op, nb_cores=4, last_wait=None):
    ctx = _context(nb_cores, **OVERRIDES.get(op, {}))
    try:
        dev = _dev(ctx)
        if last_wait is not None:
            _finish_wait(ctx, dev, last_wait)
        before = {k: dev.stats[k] for k in KEYS}
        out = OPERATIONS[op](ctx)
        assert dev._backlog == []
        return out, {k: dev.stats[k] - before[k] for k in KEYS}
    finally:
        ctx.fini()


@pytest.mark.parametrize("op", sorted(OPERATIONS))
def test_an_operation_is_bit_equal_however_its_sets_are_cut(
        monkeypatch, op):
    """The bound at two tiles' bytes cuts every wide front into chunks;
    the bound above every set is the pass before chunks.  Same tasks,
    same kernels, same tiles: the results are equal to the bit, and so
    are the tiles and bytes staged."""
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", ABOVE_EVERY_SET)
    whole, d0 = _run(op)
    assert d0["tasks_ahead_of_copy"] == 0
    tile = 32 * 32 * 4
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", 2 * tile)
    cut, d1 = _run(op)
    for a, b in zip(whole, cut):
        np.testing.assert_array_equal(a, b)
    assert d1["tasks"] == d0["tasks"]
    assert d1["stage_in_tiles"] == d0["stage_in_tiles"]
    assert d1["stage_in_bytes"] == d0["stage_in_bytes"]
    assert d1["stage_chunks"] > d0["stage_chunks"]
    assert d1["tasks_ahead_of_copy"] > 0


@pytest.mark.parametrize("op", sorted(OPERATIONS))
def test_an_operation_is_bit_equal_whichever_way_the_last_wait_read(
        monkeypatch, op):
    """The bound at two tiles' bytes, so the larger one at sixteen: a
    manager that never waited for its chip takes the sets between them
    whole, one its chip made wait cuts them.  The results are equal to
    the bit, and so are the tiles and bytes staged."""
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", 2 * 32 * 32 * 4)
    whole, d0 = _run(op, last_wait=MANAGER_BOUND)
    cut, d1 = _run(op, last_wait=CHIP_BOUND)
    for a, b in zip(whole, cut):
        np.testing.assert_array_equal(a, b)
    assert d0["tasks"] == d1["tasks"]
    assert d0["stage_in_tiles"] == d1["stage_in_tiles"]
    assert d0["stage_in_bytes"] == d1["stage_in_bytes"]
    assert d0["sets_whole_by_wait"] > 0 == d1["sets_whole_by_wait"]
    assert d0["stage_chunks"] < d1["stage_chunks"]
    assert d0["tasks_ahead_of_copy"] < d1["tasks_ahead_of_copy"]


def test_small_tiles_take_the_path_before_chunks_count_for_count(
        monkeypatch):
    """``ops.dpotrf`` at NB = 32 never reaches the module's bound: with
    one worker (so that the drained sets repeat) its puts, its stacked
    calls and its tasks a call are those of a run with the bound above
    every set."""
    got, d = _run("dpotrf", nb_cores=1)
    monkeypatch.setattr(tpu, "STAGE_CHUNK_BYTES", ABOVE_EVERY_SET)
    want, d0 = _run("dpotrf", nb_cores=1)
    np.testing.assert_array_equal(got[0], want[0])
    assert d == d0
    assert d["tasks_ahead_of_copy"] == 0
    assert d["stage_chunks"] == d["stage_in_transfers"] < d["stage_in_tiles"]
    assert d["dispatch_tasks"] / (d["batches"] + d["dispatch_tasks"]
                                  - d["batched_tasks"]) > 1


def test_the_metric_reads_the_counter_in_every_cell():
    """``tasks_ahead_of_copy_per_call``: listed for every cell under
    the layer the other stage-in readers use; the counter over the calls
    counted; nothing where the program has no such counter (the
    parent)."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from perfbench import spec
    bench = spec.load_benchmark()
    entry, = (m for m in bench["per_layer"]
              if m["name"] == "tasks_ahead_of_copy_per_call")
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert entry["moves"] == "factor_s" and entry["better"] == "higher"
    assert entry["layer"] == next(
        m["layer"] for m in bench["per_layer"]
        if m["name"] == "tiles_per_transfer")
    read = spec.metric_reader("tasks_ahead_of_copy_per_call").read
    assert read({"counters": {"tasks_ahead_of_copy": 606}, "n_counted": 2}) \
        == 303.0
    assert read({"counters": {"tasks_ahead_of_copy": 0}, "n_counted": 2}) == 0
    assert read({"counters": {"tasks": 816}, "n_counted": 2}) is None
