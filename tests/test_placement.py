"""Owner-computes placement (``devices/device.get_best_device``): a task
runs on the accelerator that owns the tile it writes; load decides only
a tile's first touch.  Counts only, on the virtual devices of the CPU
backend: no time is asserted.
"""
from types import SimpleNamespace

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import ops
from parsec_tpu.collections import BlockColumnCyclic, TwoDimBlockCyclic
from parsec_tpu.data.data import FlowAccess
from parsec_tpu.devices import get_best_device
from parsec_tpu.devices.device import PLACED_BY, Device
from parsec_tpu.devices.template import template_chore_hook
from parsec_tpu.devices.tpu import JaxDevice
from parsec_tpu.utils.params import params

N, NB = 256, 32
NT = N // NB
TILE_BYTES = NB * NB * 4
N_TILES = NT * (NT + 1) // 2            # dpotrf touches the lower triangle
N_TASKS = NT + NT * (NT - 1) + NT * (NT - 1) * (NT - 2) // 6


def _accel(ctx):
    return [d for d in ctx.devices if d.device_type == "tpu"]


def _stat(devs, key):
    return sum(d.stats[key] for d in devs)


def _spd():
    return TwoDimBlockCyclic(N, N, NB, NB, dtype=np.float32).from_numpy(
        ops.make_spd(N))


def _general():
    rng = np.random.default_rng(7)
    return TwoDimBlockCyclic(N, N, NB, NB, dtype=np.float32).from_numpy(
        rng.standard_normal((N, N)).astype(np.float32))


def _columns():
    rng = np.random.default_rng(9)
    return BlockColumnCyclic(N, N, NB, NB, dtype=np.float32).from_numpy(
        rng.standard_normal((N, N)).astype(np.float32))


@pytest.fixture
def writers(monkeypatch):
    """Every (tile, device) pair of a task handed to an accelerator
    with the tile among the flows it writes: ``{id(data): (data, {device
    indices})}``, recorded at ``kernel_scheduler``."""
    seen = {}
    submit = JaxDevice.kernel_scheduler

    def recording(self, es, task):
        for flow in task.task_class.flows:
            if flow.ctl or not task.access_of(flow) & FlowAccess.WRITE:
                continue
            din = task.data[flow.flow_index].data_in
            if din is not None and din.data is not None:
                seen.setdefault(id(din.data), (din.data, set()))[1].add(
                    self.device_index)
        return submit(self, es, task)

    monkeypatch.setattr(JaxDevice, "kernel_scheduler", recording)
    return seen


# --------------------------------------------------------------------- #
# (a) a tile's writers all ran on one accelerator                       #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("op, make", [
    (ops.dpotrf, _spd), (ops.dgeqrf, _general), (ops.dpotrf_dtd, _spd),
    (ops.dgetrf_1d, _columns)],
    ids=["dpotrf", "dgeqrf", "dpotrf_dtd", "dgetrf_1d"])
def test_every_writer_of_a_tile_ran_on_one_accelerator(ctx4, writers,
                                                       op, make):
    devs = _accel(ctx4)
    assert len(devs) > 1
    op(ctx4, make())
    assert len(writers) >= NT
    hopped = {data.key: sorted(where)
              for data, where in writers.values() if len(where) > 1}
    assert not hopped
    # and the tiles are spread: placement did not collapse on one chip
    assert len({next(iter(w)) for _, w in writers.values()}) > 1
    assert sum(_stat(devs, rule) for rule in PLACED_BY) \
        == _stat(devs, "tasks")
    # nobody advised; ops.dgetrf_1d lays its block columns out over the
    # accelerators itself (HPL's 1 x Q grid: tests/test_dgetrf_1d_4chip.py)
    assert _stat(devs, "placed_by_advice") \
        == (NT if op is ops.dgetrf_1d else 0)


# --------------------------------------------------------------------- #
# (b) what crosses between chips is read, never written                 #
# --------------------------------------------------------------------- #
def test_no_tile_is_pulled_because_a_task_wrote_it_elsewhere(
        ctx4, monkeypatch):
    from parsec_tpu.data.data import Data, is_device_array
    devs = _accel(ctx4)
    pulled_to_write = []
    start = Data.start_transfer_ownership

    def recording(self, device_id, access):
        src = start(self, device_id, access)
        if src is not None and access & FlowAccess.WRITE \
                and is_device_array(src.payload):
            pulled_to_write.append((self.key, src.device_id, device_id))
        return src

    monkeypatch.setattr(Data, "start_transfer_ownership", recording)
    ops.dpotrf(ctx4, _spd())
    assert pulled_to_write == []
    # each tile is pulled to another chip at most once per chip: a panel
    # tile is read only in its final version
    peer = _stat(devs, "stage_in_peer_bytes")
    assert 0 < peer <= (len(devs) - 1) * TILE_BYTES * N_TILES
    # and the host's copy of each tile is staged in (once; the
    # prefetcher may stage one that stage-in stages again)
    assert _stat(devs, "stage_in_bytes") - peer >= TILE_BYTES * N_TILES


# --------------------------------------------------------------------- #
# (c) the counters add up                                               #
# --------------------------------------------------------------------- #
def _advise_none(devs, A):
    return 0


def _advise_lower(devs, A):
    """Every tile dpotrf writes advised to a device: no first touch is
    left to load."""
    for m in range(NT):
        for n in range(m + 1):
            devs[(m + 2 * n) % len(devs)].data_advise(
                A.data_of(m, n), "preferred_device")
    return N_TILES


def _advise_diagonal(devs, A):
    for m in range(NT):
        devs[m % len(devs)].data_advise(A.data_of(m, m), "preferred_device")
    return NT


@pytest.mark.parametrize("advise", [_advise_none, _advise_lower,
                                    _advise_diagonal],
                         ids=["no-advice", "all-advised", "some-advised"])
def test_placement_counters_add_up_to_the_tasks_placed(ctx4, advise):
    devs = _accel(ctx4)
    A = _spd()
    advised = advise(devs, A)
    ops.dpotrf(ctx4, A)
    assert _stat(devs, "tasks") == N_TASKS
    assert sum(_stat(devs, rule) for rule in PLACED_BY) == N_TASKS
    # every dpotrf task writes one tile; advice, else load, decides only
    # a tile's first touch, and each is counted under its own rule
    assert _stat(devs, "placed_by_advice") == advised
    assert _stat(devs, "placed_by_load") == N_TILES - advised
    assert _stat(devs, "placed_by_owner") == N_TASKS - N_TILES
    # a second factorization of a refilled matrix touches each tile
    # first again: from_numpy hands the tiles back to the host, and the
    # advice stays with the tile
    ops.dpotrf(ctx4, A.from_numpy(ops.make_spd(N)))
    assert _stat(devs, "placed_by_advice") == 2 * advised
    assert _stat(devs, "placed_by_load") == 2 * (N_TILES - advised)


# --------------------------------------------------------------------- #
# (d) one accelerator: nothing to decide                                #
# --------------------------------------------------------------------- #
def test_one_accelerator_in_the_context_is_the_chosen_one():
    with params.cmdline_override("device_tpu_max", "1"):
        ctx = parsec_tpu.init(nb_cores=4)
    try:
        devs = _accel(ctx)
        assert len(devs) == 1
        ops.dpotrf(ctx, _spd())
        assert devs[0].stats["tasks"] == N_TASKS
        assert [devs[0].stats[rule] for rule in PLACED_BY] == [0, 0, 0]
    finally:
        ctx.fini()


# --------------------------------------------------------------------- #
# (e) advice decides a first touch                                      #
# --------------------------------------------------------------------- #
def test_an_advised_tile_gets_its_first_writer_where_advised(ctx4, writers):
    devs = _accel(ctx4)
    A = _spd()
    want = {}
    for m in range(NT):
        for n in range(m + 1):
            dev = devs[(m + 2 * n) % len(devs)]
            dev.data_advise(A.data_of(m, n), "preferred_device")
            want[A.data_of(m, n).key] = {dev.device_index}
    ops.dpotrf(ctx4, A)
    assert {data.key: where for data, where in writers.values()} == want
    assert _stat(devs, "placed_by_advice") == N_TILES
    assert _stat(devs, "placed_by_load") == 0


# --------------------------------------------------------------------- #
# (f) balance                                                           #
# --------------------------------------------------------------------- #
def test_no_accelerator_runs_most_of_a_dag(ctx4):
    devs = _accel(ctx4)
    ops.dpotrf(ctx4, _spd())
    assert N_TASKS >= 100
    ran = [d.stats["tasks"] for d in devs if d.stats["tasks"]]
    assert len(ran) > 1
    assert max(ran) <= 0.6 * N_TASKS, ran


# --------------------------------------------------------------------- #
# (g) prefetch advice is the counted prestage                           #
# --------------------------------------------------------------------- #
PREFETCH_KEYS = ("stage_in_bytes", "stage_in_transfers", "stage_in_tiles")


def _axpy_on(ctx, x, y):
    """One device task ``y += x`` over two tiles of a fresh DTD pool,
    inserted by the caller's ``insert()``: (the pool, x tile, y tile,
    insert)."""
    import jax
    from parsec_tpu import dtd
    from parsec_tpu.dsl.dtd import INOUT, INPUT, unpack_args
    tp = dtd.taskpool_new()
    ctx.add_taskpool(tp)

    def host(es, task):
        yy, xx = unpack_args(task)
        yy += xx

    tc = tp.create_task_class("AXPY", 2, host)
    tp.add_chore(tc, "tpu", jax.jit(lambda yy, xx: yy + xx))
    tx, ty = tp.tile_of_array(x), tp.tile_of_array(y)
    return tp, tx, ty, lambda: tp.insert_task_with_task_class(
        tc, (ty, INOUT), (tx, INPUT))


def _coherency(data):
    return sorted((c.device_id, c.coherency, c.version)
                  for c in data.copies() if c.payload is not None)


def test_prefetch_advice_is_a_counted_prestage():
    """``data_advise(d, "prefetch")`` is ``prestage_data``: the copy
    lands SHARED at the host copy's version, counted as one tile in one
    transfer; a second advice moves nothing, and the task that then
    reads the tiles stages no byte."""
    from parsec_tpu.data.data import Coherency
    with params.cmdline_override("device_tpu_max", "1"):
        ctx = parsec_tpu.init(nb_cores=2)
    try:
        dev, = _accel(ctx)
        x = np.full((NB, NB), 2.0, np.float32)
        y = np.ones((NB, NB), np.float32)
        tp, tx, ty, insert = _axpy_on(ctx, x, y)
        before = {k: dev.stats[k] for k in PREFETCH_KEYS}
        for n, tile in enumerate((tx, ty), 1):
            dev.data_advise(tile.data, "prefetch")
            copy = tile.data.get_copy(dev.device_index)
            assert copy.coherency == Coherency.SHARED
            assert copy.version == tile.data.get_copy(0).version
            assert [dev.stats[k] - before[k] for k in PREFETCH_KEYS] \
                == [n * TILE_BYTES, n, n]
            dev.data_advise(tile.data, "prefetch")     # current: a no-op
            assert [dev.stats[k] - before[k] for k in PREFETCH_KEYS] \
                == [n * TILE_BYTES, n, n]
        assert dev.mem_used == 2 * TILE_BYTES           # reserved
        staged = {k: dev.stats[k] for k in PREFETCH_KEYS}
        insert()
        tp.wait()
        assert dev.stats["tasks"] == 1
        assert {k: dev.stats[k] for k in PREFETCH_KEYS} == staged
        assert dev.stats["prefetch_hits"] == 2
        np.testing.assert_array_equal(
            np.asarray(ty.data.sync_to_host().payload), 3.0)
    finally:
        ctx.fini()


def test_prefetch_advice_leaves_a_current_or_chip_held_tile_alone(ctx4):
    """The advice on a tile whose copy here is current (the owner's),
    or whose newest copy is another chip's, stages nothing and changes
    no coherency state: only host bytes are prestaged."""
    devs = _accel(ctx4)
    here, other = devs[1], devs[0]
    x = np.full((NB, NB), 2.0, np.float32)
    y = np.ones((NB, NB), np.float32)
    tp, tx, ty, insert = _axpy_on(ctx4, x, y)
    here.data_advise(ty.data, "preferred_device")
    insert()
    tp.wait()
    assert here.stats["tasks"] == 1     # y's newest copy is ``here``'s
    for dev in (here, other):
        state = _coherency(ty.data)
        before = {k: dev.stats[k] for k in PREFETCH_KEYS + ("evictions",)}
        used = dev.mem_used
        dev.data_advise(ty.data, "prefetch")
        assert _coherency(ty.data) == state
        assert {k: dev.stats[k] for k in before} == before
        assert dev.mem_used == used
    assert ty.data.get_copy(other.device_index) is None
    np.testing.assert_array_equal(
        np.asarray(ty.data.sync_to_host().payload), 3.0)


# --------------------------------------------------------------------- #
# the rule itself, on hand-made tasks                                   #
# --------------------------------------------------------------------- #
class _Dev(Device):
    def __init__(self, index, load=0.0):
        super().__init__("tpu", index)
        self.device_load = load
        self.stats = dict.fromkeys(PLACED_BY, 0)


def _task(*flows, time_estimate=None):
    """``flows``: (access, owner_device, preferred_device), or (access,
    None) for a detached NEW copy, or "ctl"."""
    specs, refs = [], []
    for i, f in enumerate(flows):
        if f == "ctl":
            specs.append(SimpleNamespace(ctl=True, flow_index=i,
                                         access=FlowAccess.NONE))
            refs.append(SimpleNamespace(data_in=None))
            continue
        access, owner, *pref = f
        data = None if owner is None else SimpleNamespace(
            owner_device=owner, preferred_device=pref[0] if pref else -1)
        specs.append(SimpleNamespace(ctl=False, flow_index=i, access=access))
        refs.append(SimpleNamespace(data_in=SimpleNamespace(data=data)))
    return SimpleNamespace(
        task_class=SimpleNamespace(flows=specs, time_estimate=time_estimate),
        data=refs, access_of=lambda flow: flow.access)


R, W, RW = FlowAccess.READ, FlowAccess.WRITE, FlowAccess.RW


@pytest.mark.parametrize("flows, loads, chosen, rule", [
    # a GEMM: two read panel tiles on chips 1 and 2, the written tile
    # on the busiest chip: the written tile's owner wins
    ([(R, 1), (R, 2), (RW, 3)], [0, 0, 9], 3, "placed_by_owner"),
    # first touch: the written tile is the host's, the least loaded chip
    ([(R, 1), (R, 1), (RW, 0)], [5, 2, 3], 2, "placed_by_load"),
    # a tie goes to the first device of the list
    ([(RW, 0)], [1, 1, 1], 1, "placed_by_load"),
    # two written tiles (TSMQR): the first flow an accelerator owns
    ([(RW, 2), (RW, 3)], [0, 9, 0], 2, "placed_by_owner"),
    ([(RW, 0), (RW, 3)], [0, 0, 9], 3, "placed_by_owner"),
    # advice decides a first touch, whatever the load
    ([(R, 1), (RW, 0, 3)], [0, 0, 9], 3, "placed_by_advice"),
    # ... but an owner comes before advice
    ([(RW, 0, 3), (RW, 2)], [0, 0, 0], 2, "placed_by_owner"),
    # advice that names no eligible device is no advice
    ([(RW, 0, 7)], [4, 1, 2], 2, "placed_by_load"),
    # a task that writes nothing, or only detached NEW copies and CTL
    ([(R, 2), (R, 3)], [3, 2, 1], 3, "placed_by_load"),
    (["ctl", (W, None), (R, 1)], [3, 1, 2], 2, "placed_by_load"),
    # an owner that is not in the list (another device type's index)
    ([(RW, 9)], [2, 1, 3], 2, "placed_by_load"),
], ids=["owner-of-written", "first-touch-load", "tie-first",
        "two-written-first", "two-written-skip-host", "advice",
        "owner-before-advice", "advice-not-eligible", "writes-nothing",
        "new-and-ctl", "owner-not-eligible"])
def test_rule(flows, loads, chosen, rule):
    devs = [_Dev(i + 1, load) for i, load in enumerate(loads)]
    got = get_best_device(_task(*flows), devs, eligible_types={"tpu"})
    assert got.device_index == chosen
    assert [d.stats for d in devs] \
        == [dict(dict.fromkeys(PLACED_BY, 0), **{rule: int(d is got)})
            for d in devs]


def test_rule_uses_the_class_estimate_on_a_first_touch():
    devs = [_Dev(1, 1.0), _Dev(2, 2.0)]
    task = _task((RW, 0),
                 time_estimate=lambda task, dev: 5.0 / dev.device_index)
    assert get_best_device(task, devs).device_index == 2   # 1+5 > 2+2.5


def test_rule_skips_devices_of_another_type():
    cpu = Device("cpu", 0)
    devs = [cpu, _Dev(1, 9.0), _Dev(2, 1.0)]
    assert get_best_device(_task((RW, 0)), devs,
                           eligible_types={"tpu"}).device_index == 2
    # the host owning the tile is first touch, not an owner to follow
    assert devs[2].stats == {"placed_by_owner": 0, "placed_by_advice": 0,
                             "placed_by_load": 1}
    # one eligible device: returned as it is, nothing counted
    only = get_best_device(_task((RW, 2)), devs[:2], eligible_types={"tpu"})
    assert only is devs[1]
    assert devs[1].stats == dict.fromkeys(PLACED_BY, 0)


def test_a_device_selector_overrides_the_rule():
    devs = [_Dev(1), _Dev(2)]
    ran = []
    for d in devs:
        d.kernel_scheduler = lambda es, task, d=d: ran.append(d.device_index)
    hook = template_chore_hook("tpu", device_selector=lambda task, ds: ds[0])
    es = SimpleNamespace(context=SimpleNamespace(devices=devs))
    hook(es, _task((RW, 2)))
    assert ran == [1]
    assert all(v == 0 for d in devs for v in d.stats.values())
