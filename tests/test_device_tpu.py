"""XLA device module tests: stage-in/out, coherency across host/device,
async completion, LRU accounting (mirrors reference tests/dsl/dtd CUDA
variants, e.g. dtd_test_task_insert_cuda — run here on the virtual CPU
platform; the same path drives real TPU chips).
"""
import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import dtd
from parsec_tpu.dsl.dtd import INOUT, INPUT, VALUE, unpack_args
from parsec_tpu.profiling.pins import PinsEvent, PinsModule


@pytest.fixture
def jctx():
    c = parsec_tpu.init(nb_cores=2, enable_tpu=True)
    yield c
    c.fini()


def _jax_devices(ctx):
    return [d for d in ctx.devices if d.device_type == "tpu"]


def test_devices_attached(jctx):
    devs = _jax_devices(jctx)
    assert len(devs) >= 1  # conftest forces 8 virtual CPU devices
    assert jctx.devices[0].device_type == "cpu"


def test_tpu_chore_runs_and_writes_back(jctx):
    import jax.numpy as jnp
    tp = dtd.taskpool_new()
    jctx.add_taskpool(tp)
    a = np.arange(16.0, dtype=np.float32).reshape(4, 4)
    tile = tp.tile_of_array(a.copy())

    def body(es, task):  # CPU fallback
        (x,) = unpack_args(task)
        x *= 2.0

    tp.insert_task(body, (tile, INOUT))  # creates the class, runs on CPU
    tp.wait()

    tp2 = dtd.taskpool_new()
    jctx.add_taskpool(tp2)
    tile2 = tp2.tile_of_data(tile.data)

    def body2(es, task):
        (x,) = unpack_args(task)
        x *= 2.0

    tp2.insert_task(body2, (tile2, INOUT))
    tp2.add_chore(body2, "tpu", lambda x: x * 2.0)
    # chore added after the first insert applies to subsequent executions:
    tp2.insert_task(body2, (tile2, INOUT))
    tp2.data_flush(tile2)
    tp2.wait()
    np.testing.assert_allclose(np.asarray(tile.data.get_copy(0).payload),
                               a * 8.0)


def test_device_write_then_host_read_pulls_back(jctx):
    """Coherency: host body after a device body must see the new version."""
    tp = dtd.taskpool_new()
    jctx.add_taskpool(tp)
    tile = tp.tile_of_array(np.ones((8, 8), dtype=np.float32))
    seen = []

    def dev_body(es, task):
        (x,) = unpack_args(task)
        x += 1.0

    tp.insert_task(dev_body, (tile, INOUT))
    tp.add_chore(dev_body, "tpu", lambda x: x + 1.0)

    def host_body(es, task):
        (x,) = unpack_args(task)
        seen.append(np.asarray(x).copy())

    tp.insert_task(dev_body, (tile, INOUT))   # runs on device
    tp.insert_task(host_body, (tile, INPUT))  # must pull newest to host
    tp.wait()
    assert len(seen) == 1
    np.testing.assert_allclose(seen[0], np.full((8, 8), 3.0))


def test_chain_on_device_stays_on_device(jctx):
    """A chain of device tasks should not bounce through the host."""
    tp = dtd.taskpool_new()
    jctx.add_taskpool(tp)
    tile = tp.tile_of_array(np.zeros((4,), dtype=np.float32))

    def body(es, task):
        (x,) = unpack_args(task)
        x += 1.0

    tp.insert_task(body, (tile, INOUT))
    tp.add_chore(body, "tpu", lambda x: x + 1.0)
    for _ in range(9):
        tp.insert_task(body, (tile, INOUT))
    tp.data_flush(tile)
    tp.wait()
    np.testing.assert_allclose(np.asarray(tile.data.get_copy(0).payload),
                               np.full((4,), 10.0))
    devs = _jax_devices(jctx)
    total_in = sum(d.stats["stage_in_bytes"] for d in devs)
    # first stage-in is 16 bytes; a host bounce per task would be 10x that
    assert total_in <= 16 * len(devs) * 2


def test_load_balancing_spreads_independent_tiles(jctx):
    devs = _jax_devices(jctx)
    if len(devs) < 2:
        pytest.skip("needs multiple XLA devices")
    tp = dtd.taskpool_new()
    jctx.add_taskpool(tp)
    tiles = [tp.tile_of_array(np.zeros((16, 16), dtype=np.float32))
             for _ in range(16)]

    def body(es, task):
        (x,) = unpack_args(task)
        x += 1.0

    tp.insert_task(body, (tiles[0], INOUT))
    tp.add_chore(body, "tpu", lambda x: x + 1.0)
    for t in tiles[1:]:
        tp.insert_task(body, (t, INOUT))
    tp.wait()
    used = sum(1 for d in devs if d.executed_tasks > 0)
    assert used >= 2, f"all tasks landed on one device: {[d.executed_tasks for d in devs]}"


# --------------------------------------------------------------------- #
# batched dispatch + prefetch pipeline (ISSUE 5)                        #
# --------------------------------------------------------------------- #
def _burst_ctx(**over):
    """Single-worker context with one XLA device: the submitting thread
    accumulates the whole burst deterministically before the flush."""
    import parsec_tpu
    from parsec_tpu.utils.params import params
    import contextlib
    stack = contextlib.ExitStack()
    stack.enter_context(params.cmdline_override("device_tpu_max", "1"))
    for k, v in over.items():
        stack.enter_context(params.cmdline_override(k, str(v)))
    c = parsec_tpu.init(nb_cores=1)
    return c, stack


def _gemm_burst(ctx, burst, nb, seed=0):
    """Insert a same-class burst of independent c -= a @ b.T tasks;
    returns the c tiles (host np arrays read back after wait)."""
    import jax
    import jax.numpy as jnp
    tp = dtd.taskpool_new()
    ctx.add_taskpool(tp)

    def body(es, task):
        c, a, b = unpack_args(task)
        c -= a @ b.T

    boot = tp.tile_of_array(np.zeros((nb, nb), np.float32))
    tp.insert_task(body, (boot, INOUT), (boot, INPUT), (boot, INPUT))
    tp.add_chore(body, "tpu", jax.jit(
        lambda c, a, b: c - jnp.dot(a, b.T,
                                    preferred_element_type=jnp.float32)))
    rng = np.random.RandomState(seed)
    tiles = [[tp.tile_of_array(rng.rand(nb, nb).astype(np.float32))
              for _ in range(3)] for _ in range(burst)]
    for c, a, b in tiles:
        tp.insert_task(body, (c, INOUT), (a, INPUT), (b, INPUT))
    for c, a, b in tiles:
        tp.data_flush(c)
    tp.wait()
    return [np.asarray(c.data.get_copy(0).payload).copy()
            for c, _a, _b in tiles]


def test_batched_dispatch_bit_exact_vs_per_task():
    """A same-class burst through the stacked (unroll) batched path must
    produce byte-identical results to per-task dispatch, and must
    actually have batched (occupancy >= 2, multiple tasks/dispatch)."""
    ctx, st = _burst_ctx(device_batch_max=1)
    try:
        ref = _gemm_burst(ctx, 24, 32)
        devs = _jax_devices(ctx)
        assert sum(d.stats["batches"] for d in devs) == 0
    finally:
        ctx.fini()
        st.close()
    ctx, st = _burst_ctx(device_batch_max=8, device_prefetch_depth=4)
    try:
        got = _gemm_burst(ctx, 24, 32)
        devs = _jax_devices(ctx)
        batches = sum(d.stats["batches"] for d in devs)
        batched_tasks = sum(d.stats["batched_tasks"] for d in devs)
        assert batches > 0, "burst never took the batched path"
        assert batched_tasks / batches >= 2
        assert sum(d.stats["prefetch_issued"] for d in devs) > 0
        assert sum(d.stats["prefetch_hits"] for d in devs) > 0
    finally:
        ctx.fini()
        st.close()
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)


def test_batched_dispatch_value_params_group_by_value():
    """VALUE params are static: tasks passing different scalars must not
    stack into one group (the scalar is baked into the traced call)."""
    import jax
    ctx, st = _burst_ctx(device_batch_max=8)
    try:
        tp = dtd.taskpool_new()
        ctx.add_taskpool(tp)

        def body(es, task):
            args = unpack_args(task)
            x, s = args[0], task.user[1].value
            x *= s

        boot = tp.tile_of_array(np.ones((4,), np.float32))
        tp.insert_task(body, (boot, INOUT), (1.0, VALUE))
        tp.add_chore(body, "tpu", jax.jit(lambda x, s: x * s))
        tiles = [tp.tile_of_array(np.ones((4,), np.float32))
                 for _ in range(8)]
        for i, t in enumerate(tiles):
            tp.insert_task(body, (t, INOUT), (float(i % 2 + 2), VALUE))
        for t in tiles:
            tp.data_flush(t)
        tp.wait()
        for i, t in enumerate(tiles):
            np.testing.assert_allclose(
                np.asarray(t.data.get_copy(0).payload),
                np.full((4,), float(i % 2 + 2), np.float32))
    finally:
        ctx.fini()
        st.close()


def test_batched_dispatch_shape_divergent_falls_back():
    """Same class, divergent tile shapes: every shape group dispatches
    correctly (singletons ride the per-task path transparently)."""
    import jax
    import jax.numpy as jnp
    ctx, st = _burst_ctx(device_batch_max=8)
    try:
        tp = dtd.taskpool_new()
        ctx.add_taskpool(tp)

        def body(es, task):
            (x,) = unpack_args(task)
            x += 1.0

        boot = tp.tile_of_array(np.zeros((2, 2), np.float32))
        tp.insert_task(body, (boot, INOUT))
        tp.add_chore(body, "tpu", jax.jit(lambda x: x + jnp.float32(1.0)))
        shapes = [(3, 3), (5, 5), (3, 3), (5, 5), (7, 7), (3, 3)]
        tiles = [tp.tile_of_array(np.zeros(s, np.float32)) for s in shapes]
        for t in tiles:
            tp.insert_task(body, (t, INOUT))
        for t in tiles:
            tp.data_flush(t)
        tp.wait()
        for s, t in zip(shapes, tiles):
            np.testing.assert_array_equal(
                np.asarray(t.data.get_copy(0).payload),
                np.ones(s, np.float32))
    finally:
        ctx.fini()
        st.close()


def test_untraceable_body_falls_back_per_task():
    """A device chore that is not jax-traceable (host numpy inside) must
    permanently downgrade to per-task dispatch, not fail the DAG."""
    ctx, st = _burst_ctx(device_batch_max=4)
    try:
        tp = dtd.taskpool_new()
        ctx.add_taskpool(tp)

        def body(es, task):
            (x,) = unpack_args(task)
            x += 1.0

        def hostile(x):
            # np.asarray on a tracer raises: untraceable on purpose
            return x + np.asarray(np.ones(np.asarray(x).shape,
                                          np.float32))

        boot = tp.tile_of_array(np.zeros((4,), np.float32))
        tp.insert_task(body, (boot, INOUT))
        tp.add_chore(body, "tpu", hostile)
        tiles = [tp.tile_of_array(np.zeros((4,), np.float32))
                 for _ in range(8)]
        for t in tiles:
            tp.insert_task(body, (t, INOUT))
        for t in tiles:
            tp.data_flush(t)
        tp.wait()
        for t in tiles:
            np.testing.assert_array_equal(
                np.asarray(t.data.get_copy(0).payload),
                np.ones((4,), np.float32))
        chore = next(c for c in tp.task_classes[0].incarnations
                     if c.device_type == "tpu")
        assert chore.batch_spec is not None
        assert not chore.batch_spec.batchable   # permanently downgraded
    finally:
        ctx.fini()
        st.close()


# --------------------------------------------------------------------- #
# TPUDevice.drain() error paths (ISSUE 5 satellite): an async kernel    #
# failure in a trailing eager-window entry must surface via             #
# record_task_error / raise_pending_error, not vanish                   #
# --------------------------------------------------------------------- #
class _FailingArray:
    """A stub in-flight output whose readiness poll succeeds but whose
    completion wait raises — the shape of an async XLA kernel failure."""

    def is_ready(self):
        return True

    def is_deleted(self):
        return False

    def block_until_ready(self):
        raise RuntimeError("injected async kernel failure")


class _StubTask:
    taskpool = None

    def snprintf(self):
        return "STUB(0)"


def _file_call(dev, n, make_array):
    """File, by hand, the window record of one device call of ``n``
    tasks whose outputs are ``make_array()`` each."""
    from parsec_tpu.devices.tpu import _InFlight
    tasks = [_StubTask() for _ in range(n)]
    rec = _InFlight(tasks, [make_array() for _ in range(n)],
                    [[0]] * n, float(n))
    dev._window.append(rec)
    dev._window_tasks += n
    return rec


@pytest.mark.parametrize("n", [1, 4])
def test_drain_records_async_error_on_context(jctx, n):
    """A failing call of n tasks is waited for ONCE and leaves ONE task
    error, against a task of the call."""
    dev = _jax_devices(jctx)[0]
    _file_call(dev, n, _FailingArray)
    retired0 = dev.stats["retired_calls"]
    load0 = dev.device_load
    dev.drain(jctx)
    assert dev._window == [] and dev._window_tasks == 0
    assert dev.stats["retired_calls"] == retired0 + 1
    assert dev.device_load <= load0   # load contribution dropped
    assert len(jctx._task_errors) == 1, \
        "drain swallowed the async kernel failure, or counted it per task"
    with pytest.raises(RuntimeError, match="task body failed"):
        jctx.raise_pending_error()
    jctx._task_errors.clear()   # let fini() tear down cleanly


@pytest.mark.parametrize("n", [1, 4])
def test_drain_without_context_logs_not_raises(jctx, n):
    """Teardown drain (no context): the failure must be logged, never
    propagated out of fini/drain."""
    dev = _jax_devices(jctx)[0]
    _file_call(dev, n, _FailingArray)
    dev.drain()   # must not raise
    assert dev._window == [] and dev._window_tasks == 0
    assert not jctx._task_errors


@pytest.mark.parametrize("n", [1, 4])
def test_fini_retires_failing_call_quietly(n):
    """``fini`` retires what the window still holds, call by call, and
    a failing call is logged there, not raised."""
    ctx = parsec_tpu.init(nb_cores=2, enable_tpu=True)
    dev = _jax_devices(ctx)[0]
    _file_call(dev, n, _FailingArray)
    retired0 = dev.stats["retired_calls"]
    ctx.fini()   # must not raise
    assert dev._window == [] and dev._window_tasks == 0
    assert dev.stats["retired_calls"] == retired0 + 1


def test_drain_discards_aborted_pending(jctx):
    """Tasks stranded in the accumulation queue by a DAG abort are
    discarded (never executed) and their load contribution dropped."""
    dev = _jax_devices(jctx)[0]
    dev.load_add(2.5)
    dev.pending.push_back((_StubTask(), 2.5))
    dev.drain(jctx)
    assert len(dev.pending) == 0
    assert dev.device_load == 0.0
    assert not jctx._task_errors


class _Donated:
    def is_deleted(self):
        return True

    def is_ready(self):   # pragma: no cover - must not be reached
        raise RuntimeError("polled a deleted buffer")

    def block_until_ready(self):   # pragma: no cover - ditto
        raise RuntimeError("blocked on a deleted buffer")


@pytest.mark.parametrize("n", [1, 4])
def test_window_poll_treats_donated_buffer_as_ready(jctx, n):
    """A window entry whose outputs were donated to a successor batched
    call (buffers deleted) must retire cleanly instead of erroring:
    the successor's record covers them."""
    dev = _jax_devices(jctx)[0]
    rec = _file_call(dev, n, _Donated)
    assert rec.ready() and list(rec.live()) == []
    dev.drain(jctx)
    assert not jctx._task_errors


def test_call_waits_on_first_output_not_donated_onward(jctx):
    """One wait per call, on an output that still has a buffer: with
    the first output donated, the second answers for the executable."""
    waited = []

    class _Live:
        def is_deleted(self):
            return False

        def is_ready(self):
            return True

        def block_until_ready(self):
            waited.append(self)

    outs = [_Donated(), _Live(), _Live(), _Live()]
    dev = _jax_devices(jctx)[0]
    rec = _file_call(dev, 4, iter(outs).__next__)
    assert rec.ready()
    dev.drain(jctx)
    assert waited == [outs[1]]
    assert not jctx._task_errors


# --------------------------------------------------------------------- #
# one record per device call (ISSUE 29): a stacked call of n tasks is   #
# filed, waited on, retired and completed once                          #
# --------------------------------------------------------------------- #
def _one_core_burst(ctx, burst, nb=16, chain=False):
    """``burst`` independent GEMM tasks of one class with a device
    chore, inserted before the single worker (the caller, in wait())
    runs any: they accumulate in the device's queue and flush in
    groups of device_batch_max.  With ``chain`` each is followed by a
    host task NEXT on its tile.  Returns (taskpool, c tiles); see
    ``_assert_burst_result``."""
    import jax
    import jax.numpy as jnp
    tp = dtd.taskpool_new()
    ctx.add_taskpool(tp)

    def host(es, task):
        c, a, b = unpack_args(task)
        c -= a @ b.T

    def bump(es, task):
        (c,) = unpack_args(task)
        c += 1.0

    tc = tp.create_task_class("GEMM", 3, host)
    tp.add_chore(tc, "tpu", jax.jit(
        lambda c, a, b: c - jnp.dot(a, b.T,
                                    preferred_element_type=jnp.float32)))
    nxt = tp.create_task_class("NEXT", 1, bump)
    rng = np.random.RandomState(5)
    tiles = [[tp.tile_of_array(rng.rand(nb, nb).astype(np.float32))
              for _ in range(3)] for _ in range(burst)]
    for c, a, b in tiles:
        tp.insert_task_with_task_class(tc, (c, INOUT), (a, INPUT),
                                       (b, INPUT))
    if chain:
        for c, _a, _b in tiles:
            tp.insert_task_with_task_class(nxt, (c, INOUT))
    return tp, [c for c, _a, _b in tiles]


def _assert_burst_result(cs, nb=16, chain=False):
    """Every c tile of ``_one_core_burst`` holds ITS task's result."""
    rng = np.random.RandomState(5)
    for c in cs:
        c0, a, b = (rng.rand(nb, nb).astype(np.float32) for _ in range(3))
        got = np.asarray(c.data.sync_to_host().payload)
        np.testing.assert_allclose(got, c0 - a @ b.T + (1.0 if chain else 0.0),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("nb_ranks,n", [(1, 8), (2, 16)])
def test_stacked_call_retires_as_one_record(nb_ranks, n):
    """A stacked call of n tasks, on one rank and across ranks (a flush
    group is one call there too): ONE record retired, one ``epilog``
    span from ``_epilog`` and one ``chip_wait`` span from ``_retire``
    (the always-on brackets count the same), and every task still has
    its own ``complete`` span."""
    from conftest import spmd
    from parsec_tpu.comm import RemoteDepEngine
    from parsec_tpu.obs import phases
    from parsec_tpu.utils.params import params

    def body(ctx, rank):
        try:
            dev = _jax_devices(ctx)[0]
            with phases.root_span(ctx, "burst", 29 + rank):
                tp, cs = _one_core_burst(ctx, n)
                tp.wait()
                ctx.wait()      # its exit drains the window
            if rank:    # keyless tiles live on rank 0: it ran them all
                return
            rec = [r for r in phases.completed() if r["id"] == 29][-1]
            assert rec["op"] == "burst"
            assert dev.stats["tasks"] == n
            assert dev.stats["batches"] == 1
            assert dev.stats["batched_tasks"] == n
            assert dev.stats["retired_calls"] == 1
            assert rec["phases"]["epilog"]["count"] == 1
            assert rec["phases"]["chip_wait"]["count"] == 1
            assert [rec["manager"][b]["count"] for b in phases.BRACKETS] \
                == [1, 1, 1, 1, 1, 1]
            assert rec["phases"]["complete"]["count"] == n
            assert rec["phases"]["release_deps"]["count"] == n
            assert dev._window == [] and dev._window_tasks == 0
            _assert_burst_result(cs)
        finally:
            ctx.fini()

    with params.cmdline_override("device_tpu_max", "1"), \
         params.cmdline_override("device_batch_max", str(n)):
        if nb_ranks == 1:
            body(parsec_tpu.init(nb_cores=1, profile=True), 0)
        else:
            spmd(nb_ranks, lambda rank, fabric: body(parsec_tpu.Context(
                nb_cores=1, profile=True,
                comm=RemoteDepEngine(fabric.engine(rank))), rank))


@pytest.mark.parametrize("batch_max,want_calls", [(4, 4), (16, 1)])
def test_window_bounds_tasks_in_flight(monkeypatch, batch_max, want_calls):
    """``EAGER_WINDOW`` bounds TASKS in flight with calls as the
    entries: at window 4 with calls of 4 the oldest call is waited for
    as the next is filed (never more than two calls' tasks in flight,
    one call's once filed), and a single call larger than the window
    waits for nothing: the window is never emptied under it."""
    from parsec_tpu.devices import tpu
    from parsec_tpu.devices.tpu import JaxDevice, _InFlight
    from parsec_tpu.utils.params import params
    monkeypatch.setattr(tpu, "EAGER_WINDOW", 4)
    # only backpressure (and the drain at wait()'s exit) retires
    monkeypatch.setattr(_InFlight, "ready", lambda self: False)
    after_filing, at_retire = [], []
    filed, retire = JaxDevice._finish_submit, JaxDevice._retire

    def filing(self, es, rec):
        filed(self, es, rec)
        after_filing.append((self._window_tasks, len(self._window)))

    def retiring(self, rec, es=None, context=None):
        # by backpressure (es given) the record has left the count
        at_retire.append((self._window_tasks + len(rec.tasks)
                          if es is not None else self._window_tasks,
                          es is not None))
        retire(self, rec, es, context)

    monkeypatch.setattr(JaxDevice, "_finish_submit", filing)
    monkeypatch.setattr(JaxDevice, "_retire", retiring)
    with params.cmdline_override("device_tpu_max", "1"), \
         params.cmdline_override("device_batch_max", str(batch_max)):
        ctx = parsec_tpu.init(nb_cores=1)
    try:
        dev = _jax_devices(ctx)[0]
        tp, cs = _one_core_burst(ctx, 16)
        tp.wait()
        ctx.wait()      # its exit drains the window
        assert dev.stats["tasks"] == 16
        assert dev.stats["batches"] == want_calls
        assert dev.stats["retired_calls"] == want_calls
        assert dev._window == [] and dev._window_tasks == 0
        if batch_max == 4:
            assert after_filing == [(4, 1)] * 4
            # three retired by backpressure, the last by the drain
            assert at_retire == [(8, True)] * 3 + [(4, False)]
        else:
            assert after_filing == [(16, 1)]
            assert at_retire == [(16, False)]
    finally:
        ctx.fini()


def test_stacked_call_async_failure_is_one_task_error(monkeypatch):
    """A stacked call whose executable fails after the dispatch: ONE
    task error, against a task of the call, and the DAG aborts."""
    from parsec_tpu.devices.tpu import JaxDevice
    from parsec_tpu.utils.params import params
    record = JaxDevice._record
    calls = []

    def failing(self, chunk, outs, how, waits=None):
        rec = record(self, chunk, outs, how, waits)
        rec.waits = [[_FailingArray() for _ in rec.tasks]]
        calls.append(rec)
        return rec

    monkeypatch.setattr(JaxDevice, "_record", failing)
    with params.cmdline_override("device_tpu_max", "1"), \
         params.cmdline_override("device_batch_max", "8"):
        ctx = parsec_tpu.init(nb_cores=1)
    try:
        tp, cs = _one_core_burst(ctx, 8)
        with pytest.raises(RuntimeError, match="task body failed") as err:
            tp.wait()
            ctx.wait()
        assert len(calls) == 1 and len(calls[0].tasks) == 8
        assert len(ctx._task_errors) == 1
        assert "injected async kernel failure" in str(err.value.__cause__)
        assert _jax_devices(ctx)[0].stats["retired_calls"] == 1
        ctx._task_errors.clear()
    finally:
        ctx.fini()


class _Recorder(PinsModule):
    """The PINS ``events`` in order, from every thread, as (event,
    payload) entries of ``log``."""

    name = "test_recorder"

    def __init__(self, events, log=None):
        self.events = list(events)
        self.log = [] if log is None else log

    def callback(self, es, event, payload):
        self.log.append((event, payload))


def test_ready_tasks_of_one_call_reach_the_scheduler_once():
    """The successors of a call's 8 tasks are handed over by ONE
    ``schedule_keep_best``: one SCHEDULE_BEGIN event (the best of them
    may stay on the releasing thread), after the last of the call's
    COMPLETE_EXEC_END events."""
    from parsec_tpu.utils.params import params
    with params.cmdline_override("device_tpu_max", "1"), \
         params.cmdline_override("device_batch_max", "8"):
        ctx = parsec_tpu.init(nb_cores=1)
    mod = _Recorder([PinsEvent.SCHEDULE_BEGIN, PinsEvent.COMPLETE_EXEC_END])
    try:
        tp, cs = _one_core_burst(ctx, 8, chain=True)
        mod.enable()
        tp.wait()
        _assert_burst_result(cs, chain=True)
    finally:
        mod.disable()
        ctx.fini()
    name = lambda t: t.task_class.name   # noqa: E731
    handovers = [(i, p) for i, (ev, p) in enumerate(mod.log)
                 if ev == PinsEvent.SCHEDULE_BEGIN
                 and any(name(t) == "NEXT" for t in p)]
    assert len(handovers) == 1, [len(p) for _i, p in handovers]
    at, tasks = handovers[0]
    assert all(name(t) == "NEXT" for t in tasks) and len(tasks) in (7, 8)
    done = [i for i, (ev, p) in enumerate(mod.log)
            if ev == PinsEvent.COMPLETE_EXEC_END and name(p) == "GEMM"]
    assert len(done) == 8 and max(done) < at
