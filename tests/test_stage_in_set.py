"""Set stage-in (``JaxDevice.prestage_many`` / ``_stage_in_set``): the
host tiles a drained ready set needs go to the chip in ONE ``device_put``
call ahead of the per-task stage-in, which then finds them resident.
Counts and values only: no time is asserted.
"""
import contextlib

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import dtd, ops
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.data.data import Coherency, data_new_with_payload
from parsec_tpu.devices.tpu import JaxDevice
from parsec_tpu.dsl.dtd import INOUT, unpack_args
from parsec_tpu.utils.params import params

NT, NB = 8, 64
LOWER = NT * (NT + 1) // 2
TILE = NB * NB * 4
KEYS = ("stage_in_tiles", "stage_in_transfers", "stage_in_bytes",
        "prefetch_issued", "prefetch_hits", "evictions")


def _spd(n, seed):
    rng = np.random.default_rng(seed)
    W = rng.standard_normal((n, 64)).astype(np.float32)
    return np.eye(n, dtype=np.float32) + W @ W.T


def _tiled(M, nb=NB):
    n = M.shape[0]
    return TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)


def _context(nb_cores=4, **over):
    """A context with ONE accelerator, so every tile is staged from the
    host and the counts are the DAG's."""
    over["device_tpu_max"] = 1
    with contextlib.ExitStack() as stack:
        for k, v in over.items():
            stack.enter_context(params.cmdline_override(k, str(v)))
        return parsec_tpu.init(nb_cores=nb_cores)


def _dev(ctx):
    dev, = (d for d in ctx.devices if d.device_type == "tpu")
    return dev


def _factor(entry, M, **over):
    """(lower factor, the accelerator's counter deltas) of ``entry`` on
    a fresh context."""
    ctx = _context(**over)
    try:
        dev = _dev(ctx)
        before = {k: dev.stats[k] for k in KEYS}
        A = _tiled(M)
        getattr(ops, entry)(ctx, A)
        L = np.tril(A.to_numpy())
        return L, {k: dev.stats[k] - before[k] for k in KEYS}
    finally:
        ctx.fini()


@pytest.mark.parametrize("entry", ["dpotrf", "dpotrf_dtd"])
def test_a_factorization_stages_each_lower_tile_once_in_set_transfers(
        entry, monkeypatch):
    """Every lower tile reaches the chip once, in fewer calls than
    tiles, through the set pass (the per-task stage-in finds each one),
    and the factor is the one the per-task stage-in alone gives, to the
    bit: semantics do not depend on the pass."""
    M = _spd(NT * NB, 11)
    L, d = _factor(entry, M)
    assert d["stage_in_tiles"] == LOWER
    assert d["stage_in_transfers"] < LOWER
    assert d["stage_in_bytes"] == LOWER * TILE
    assert d["prefetch_issued"] == d["prefetch_hits"] == LOWER
    monkeypatch.setattr(JaxDevice, "_stage_in_set", lambda self, items: None)
    alone, d0 = _factor(entry, M)
    assert d0["stage_in_tiles"] == d0["stage_in_transfers"] == LOWER
    assert d0["stage_in_bytes"] == LOWER * TILE
    assert d0["prefetch_issued"] == d0["prefetch_hits"] == 0
    np.testing.assert_array_equal(L, alone)
    np.testing.assert_allclose(L @ L.T, M, rtol=0, atol=1e-3)


def test_device_batch_max_1_still_runs_the_set_pass():
    """The pass sits above the fork to the unbatched path: with stacked
    dispatch off every tile is still staged by it, once."""
    M = _spd(NT * NB, 12)
    L, d = _factor("dpotrf", M, device_batch_max=1)
    assert d["stage_in_tiles"] == LOWER
    assert d["prefetch_issued"] == d["prefetch_hits"] == LOWER
    assert d["stage_in_bytes"] == LOWER * TILE
    np.testing.assert_allclose(L @ L.T, M, rtol=0, atol=1e-3)


@pytest.fixture
def dev():
    ctx = _context(nb_cores=1)
    yield _dev(ctx)
    ctx.fini()


def _datas(arrays):
    return [data_new_with_payload(a) for a in arrays]


def _delta(dev, before):
    return {k: dev.stats[k] - before[k] for k in KEYS}


def _snapshot(dev):
    return {k: dev.stats[k] for k in KEYS}


def test_prestage_many_moves_mixed_shapes_and_dtypes_in_one_call(dev):
    """Full tiles, edge tiles and an int32 pivot tile go out in ONE
    call, a Data named twice goes once, and the committed list is
    exactly what was staged, in values."""
    rng = np.random.default_rng(5)
    arrays = [rng.standard_normal((16, 16)).astype(np.float32)
              for _ in range(7)]
    arrays += [rng.standard_normal((16, 5)).astype(np.float32)
               for _ in range(2)]
    arrays.append(np.arange(16, dtype=np.int32))
    datas = _datas(arrays)
    before = _snapshot(dev)
    committed = dev.prestage_many(datas + datas[:3])
    d = _delta(dev, before)
    assert committed == datas
    assert (d["stage_in_transfers"], d["stage_in_tiles"]) == (1, 10)
    assert d["stage_in_bytes"] == sum(a.nbytes for a in arrays)
    assert d["prefetch_issued"] == 10
    for data, want in zip(datas, arrays):
        copy = data.get_copy(dev.device_index)
        assert copy.coherency == Coherency.SHARED and copy.version == 1
        assert dev.prestaged_current(data)
        got = np.asarray(copy.payload)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    # all resident and current now: nothing to do, nothing counted
    assert dev.prestage_many(datas) == []
    assert not dev.prestage_data(datas[0])
    assert _delta(dev, before) == d


def test_a_resident_current_tile_costs_the_pass_no_lock(dev):
    """The pass looks at every flow of every drained task: a tile whose
    copy here is the owner is passed over on ``get_copy`` and one
    compare, its lock not taken by the pass."""
    import jax
    from parsec_tpu.data.data import DataCopy, FlowAccess
    data, = _datas([np.ones((4, 4), np.float32)])
    copy = DataCopy(data, dev.device_index, payload=jax.device_put(
        np.zeros((4, 4), np.float32), dev.jax_device))
    data.attach_copy(copy)
    data.complete_transfer_ownership(dev.device_index, FlowAccess.RW)

    class Counting:
        entered = 0

        def __init__(self, lock):
            self.lock = lock

        def __enter__(self):
            Counting.entered += 1
            return self.lock.__enter__()

        def __exit__(self, *exc):
            return self.lock.__exit__(*exc)

    data._lock = Counting(data._lock)
    before = _snapshot(dev)
    assert dev.prestage_many([data] * 5) == []
    assert Counting.entered == 5      # get_copy's own, once a look
    assert _delta(dev, before)["stage_in_transfers"] == 0


def test_a_copy_made_owned_between_plan_and_commit_is_not_clobbered(
        dev, monkeypatch):
    """A stage-in that wins the race owns the coherency transition: the
    set pass leaves its copy alone, gives back the bytes it held for
    the tile and does not report it committed."""
    import jax
    from parsec_tpu.data.data import DataCopy, FlowAccess
    rng = np.random.default_rng(7)
    arrays = [rng.standard_normal((16, 16)).astype(np.float32)
              for _ in range(4)]
    datas = _datas(arrays)
    winner = datas[2]
    mine = np.full((16, 16), 3.0, np.float32)
    device_put = jax.device_put

    def racing(x, device=None, **kw):
        out = device_put(x, device, **kw)
        if isinstance(x, list):     # the set's one call: now lose a race
            copy = DataCopy(winner, dev.device_index,
                            payload=device_put(mine, dev.jax_device))
            winner.attach_copy(copy)
            winner.complete_transfer_ownership(dev.device_index,
                                               FlowAccess.RW)
        return out

    monkeypatch.setattr(jax, "device_put", racing)
    used = dev.mem_used
    before = _snapshot(dev)
    committed = dev.prestage_many(datas)
    d = _delta(dev, before)
    assert committed == [x for x in datas if x is not winner]
    copy = winner.get_copy(dev.device_index)
    assert copy.coherency == Coherency.OWNED
    np.testing.assert_array_equal(np.asarray(copy.payload), mine)
    assert not dev.prestaged_current(winner)
    assert dev.mem_used - used == 3 * arrays[0].nbytes
    assert d["stage_in_bytes"] == 3 * arrays[0].nbytes
    assert d["prefetch_issued"] == 3


def test_a_refilled_matrix_stages_the_new_values():
    """``from_numpy`` a second time bumps the host copies: the set pass
    stages every tile again, with the NEW values."""
    ctx = _context()
    try:
        dev = _dev(ctx)
        M1, M2 = _spd(NT * NB, 21), _spd(NT * NB, 22)
        A = _tiled(M1)
        ops.dpotrf(ctx, A)
        L1 = np.tril(A.to_numpy())
        before = _snapshot(dev)
        A.from_numpy(M2)
        ops.dpotrf(ctx, A)
        L2 = np.tril(A.to_numpy())
        d = _delta(dev, before)
        assert d["stage_in_tiles"] == LOWER
        assert d["stage_in_transfers"] < LOWER
        assert d["stage_in_bytes"] == LOWER * TILE
        np.testing.assert_allclose(L1 @ L1.T, M1, rtol=0, atol=1e-3)
        np.testing.assert_allclose(L2 @ L2.T, M2, rtol=0, atol=1e-3)
        assert np.abs(L1 - L2).max() > 1e-2
    finally:
        ctx.fini()


def test_a_set_evicts_through_reserve(dev):
    """A budget of ten tiles and two sets of eight: the second set
    reserves its bytes at once and the LRU drops six clean copies of
    the first, oldest first; staged again, those six read right (and
    push out the six oldest in turn)."""
    rng = np.random.default_rng(31)
    arrays = [rng.standard_normal((16, 16)).astype(np.float32)
              for _ in range(16)]
    tile = arrays[0].nbytes
    first, second = _datas(arrays[:8]), _datas(arrays[8:])
    dev.mem_budget = dev.mem_used + 10 * tile
    before = _snapshot(dev)
    assert dev.prestage_many(first) == first
    assert dev.prestage_many(second) == second
    d = _delta(dev, before)
    assert d["evictions"] == 6
    assert dev.mem_used <= dev.mem_budget
    index = dev.device_index
    for data in first[:6]:
        copy = data.get_copy(index)
        assert copy.coherency == Coherency.INVALID and copy.payload is None
    assert dev.prestage_many(first[:6]) == first[:6]
    valid = [(data.get_copy(index), want)
             for data, want in zip(first + second, arrays)
             if data.get_copy(index).coherency != Coherency.INVALID]
    assert len(valid) == 10
    assert all(data.get_copy(index).coherency == Coherency.SHARED
               for data in first[:6])
    for copy, want in valid:
        np.testing.assert_array_equal(np.asarray(copy.payload), want)
    d = _delta(dev, before)
    assert d["evictions"] == 12
    assert d["stage_in_bytes"] == 22 * tile


def test_the_set_pass_takes_host_sources_only(dev):
    """A tile whose newest copy is on a chip is not the pass's to move
    (the per-task stage-in pulls it); a Data with no payload is
    skipped."""
    import jax
    on_chip = data_new_with_payload(
        jax.device_put(np.ones((4, 4), np.float32), dev.jax_device))
    empty = data_new_with_payload(None)
    host = data_new_with_payload(np.ones((4, 4), np.float32))
    before = _snapshot(dev)
    assert dev.prestage_many([on_chip, empty, host]) == [host]
    d = _delta(dev, before)
    assert d["stage_in_transfers"] == d["stage_in_tiles"] == 1


def test_dtd_tiles_written_on_the_host_are_staged_by_the_pass():
    """A same-class DTD burst over fresh host tiles: the burst's tiles
    ride the set's calls and every one is a hit of the per-task
    stage-in."""
    ctx = _context(nb_cores=1, device_batch_max=8)
    try:
        dev = _dev(ctx)
        tp = dtd.taskpool_new()
        ctx.add_taskpool(tp)

        def body(es, task):
            (x,) = unpack_args(task)
            x += 1.0

        tc = tp.create_task_class("INC", 1, body)
        tp.add_chore(tc, "tpu", lambda x: x + 1.0)
        tiles = [tp.tile_of_array(np.full((8, 8), float(i), np.float32))
                 for i in range(8)]
        before = _snapshot(dev)
        for t in tiles:
            tp.insert_task_with_task_class(tc, (t, INOUT))
        tp.data_flush_all()
        tp.wait()
        d = _delta(dev, before)
        assert d["stage_in_tiles"] == 8
        assert d["stage_in_transfers"] < 8
        assert d["prefetch_hits"] == 8
        for i, t in enumerate(tiles):
            np.testing.assert_array_equal(
                np.asarray(t.data.get_copy(0).payload),
                np.full((8, 8), i + 1.0, np.float32))
    finally:
        ctx.fini()


def test_several_accelerators_each_stage_their_own_sets():
    """Four accelerators in one context: each manager's pass stages
    what is still on the host onto ITS device, a tile another chip owns
    is left to the per-task stage-in (a peer pull, one tile a call),
    and the factor is right."""
    M = _spd(NT * NB, 51)
    with params.cmdline_override("device_tpu_max", "4"):
        ctx = parsec_tpu.init(nb_cores=4)
    try:
        devs = [d for d in ctx.devices if d.device_type == "tpu"]
        assert len(devs) == 4
        A = _tiled(M)
        ops.dpotrf(ctx, A)
        L = np.tril(A.to_numpy())
        np.testing.assert_allclose(L @ L.T, M, rtol=0, atol=1e-3)
        total = {k: sum(d.stats[k] for d in devs) for k in KEYS}
        peer = sum(d.stats["stage_in_peer_bytes"] for d in devs)
        assert total["stage_in_bytes"] == total["stage_in_tiles"] * TILE
        assert total["stage_in_tiles"] >= LOWER
        assert total["prefetch_issued"] + peer // TILE \
            == total["stage_in_tiles"]
        assert total["prefetch_hits"] <= total["prefetch_issued"]
        for d in devs:
            # a set's tiles ride one call; a peer pull is a call a tile
            sets = d.stats["stage_in_transfers"] \
                - d.stats["stage_in_peer_bytes"] // TILE
            assert 0 <= sets <= d.stats["prefetch_issued"]
    finally:
        ctx.fini()


def test_a_mesh_device_stages_each_tile_at_its_placement():
    """One device over a 2x2 chip mesh: the set's one call carries a
    placement per tile, so each lands on its block-cyclic chip, and a
    factorization stages every lower tile once."""
    M = _spd(NT * NB, 52)
    with params.cmdline_override("device_mesh_shape", "2x2"):
        ctx = parsec_tpu.init(nb_cores=2)
    try:
        dev = ctx.device_by_type("tpu")
        A = _tiled(M)
        row = [A.data_of(NT - 1, n) for n in range(NT)]
        before = _snapshot(dev)
        assert dev.prestage_many(row) == row
        assert _delta(dev, before)["stage_in_transfers"] == 1
        chips = set()
        for data in row:
            payload = data.get_copy(dev.device_index).payload
            assert payload.devices() == {dev._chip_of(data)}
            chips |= payload.devices()
        assert len(chips) == 2      # a tile row spans a row of the grid
        ops.dpotrf(ctx, A)
        d = _delta(dev, before)
        assert d["stage_in_tiles"] == d["prefetch_issued"] == LOWER
        assert d["stage_in_transfers"] < LOWER
        assert d["stage_in_bytes"] == LOWER * TILE
        L = np.tril(A.to_numpy())
        np.testing.assert_allclose(L @ L.T, M, rtol=0, atol=1e-3)
    finally:
        ctx.fini()
