"""``ops.pdgemm_dtd``: upstream's multi-accelerator DTD GEMM
(``dtd_test_simple_gemm.c``) and what it forced: tiles advised to their
devices, an advised first touch counted on its own, a flush for the
tiles a task wrote and no other, a lone task that takes its VALUEs as
the constants they are in a stacked call.  Counts and bits only: no
time is asserted.
"""
import os
import sys

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import ops
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.data.data import FlowAccess
from parsec_tpu.devices.device import PLACED_BY
from parsec_tpu.devices.tpu import JaxDevice
from parsec_tpu.obs import phases
from parsec_tpu.ops.pdgemm_dtd import advice_grid, device_grid
from parsec_tpu.utils.params import params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import spec  # noqa: E402
from perfbench.checks.test_dgemm_dtd import LIMIT  # noqa: E402
from perfbench.reference import gemm  # noqa: E402

NB = 16
MT = NT = KT = 4
N = NT * NB
TILE_BYTES = NB * NB * 4
SEEDS = [3, 1 << 20, (1 << 31) + 5]


def _tiled(M, nb=NB):
    n = M.shape[0]
    return TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)


def _operands(inputs):
    return [_tiled(inputs[name]) for name in gemm.OPERANDS]


def _accel(ctx):
    return [d for d in ctx.devices if d.device_type == "tpu"]


def _stats(ctx, *keys):
    return [sum(d.stats[k] for d in _accel(ctx)) for k in keys]


def _context(accelerators, cores=4):
    with params.cmdline_override("device_tpu_max", str(accelerators)):
        return parsec_tpu.init(nb_cores=cores)


@pytest.fixture(scope="module", params=[1, 4], ids=["one-device",
                                                    "four-devices"])
def ctx(request):
    c = _context(request.param)
    assert len(_accel(c)) == request.param
    yield c
    c.fini()


@pytest.fixture(scope="module")
def ctx4():
    c = _context(4)
    yield c
    c.fini()


@pytest.fixture(scope="module")
def ptg_products():
    """``ops.pdgemm``'s C a seed, from calls that dispatched no task
    alone: a PTG task dispatched alone takes alpha and beta as run-time
    scalars where its stacked program has them as constants, and XLA's
    CPU backend contracts the two differently (a unit in the last
    place).  One device and one worker make a k-level reach the manager
    whole."""
    c = _context(1, cores=1)
    out = {}
    try:
        for seed in SEEDS:
            inputs = gemm.make_input(N, seed)
            for _attempt in range(8):
                A, B, C = _operands(inputs)
                before = _stats(c, "batched_tasks", "dispatch_tasks")
                ops.pdgemm(c, A, B, C, alpha=gemm.ALPHA, beta=gemm.BETA)
                stacked, all_ = (x - b for x, b in zip(
                    _stats(c, "batched_tasks", "dispatch_tasks"), before))
                if stacked == all_ == MT * NT * KT:
                    break
            else:
                pytest.fail("ops.pdgemm dispatched a task alone in each "
                            "of 8 calls")
            out[seed] = C.to_numpy()
    finally:
        c.fini()
    return out


# --------------------------------------------------------------------- #
# the product                                                           #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", SEEDS)
def test_product_against_the_plain_reference_and_bit_equal_to_pdgemm(
        ctx, ptg_products, seed):
    inputs = gemm.make_input(N, seed)
    A, B, C = _operands(inputs)
    before = _stats(ctx, "tasks", "batch_downgrades")
    ops.pdgemm_dtd(ctx, A, B, C, alpha=gemm.ALPHA, beta=gemm.BETA)
    assert [x - b for x, b in zip(_stats(ctx, "tasks", "batch_downgrades"),
                                  before)] == [MT * NT * KT, 0]
    got = C.to_numpy()
    want = gemm.plain_product(inputs, NB)
    assert np.abs(got - want).max() \
        <= 64 * np.finfo(np.float32).eps * np.abs(want).max()
    assert gemm.residual(got, gemm.expected(inputs, seed)) <= LIMIT
    # the same kernel with the same constants, k ascending on every
    # tile, alone or stacked, on whichever device
    assert np.array_equal(got, ptg_products[seed])


@pytest.mark.parametrize("shape", [(1, 1, 1), (2, 3, 1), (3, 2, 5)],
                         ids=lambda s: "x".join(map(str, s)))
def test_rectangular_grids_and_default_scalars(ctx, shape):
    mt, nt, kt = shape
    rng = np.random.default_rng(mt * 100 + nt * 10 + kt)
    a, b, c = (rng.standard_normal((r * NB, s * NB)).astype(np.float32)
               for r, s in ((mt, kt), (kt, nt), (mt, nt)))

    def coll(x):
        return TwoDimBlockCyclic(x.shape[0], x.shape[1], NB, NB,
                                 dtype=np.float32).from_numpy(x)

    C = coll(c)
    ops.pdgemm_dtd(ctx, coll(a), coll(b), C)
    want = c.astype(np.float64) + a.astype(np.float64) @ b
    assert np.abs(C.to_numpy() - want).max() <= 1e-4 * np.abs(want).max()


def test_refuses_grids_that_do_not_agree(ctx):
    inputs = gemm.make_input(N, 1)
    A, B, _ = _operands(inputs)
    C = _tiled(np.zeros((2 * NB, 2 * NB), np.float32))
    with pytest.raises(ValueError, match="tile grids do not agree"):
        ops.pdgemm_dtd(ctx, A, B, C)


# --------------------------------------------------------------------- #
# placement: advice decides the first touch, the owner the rest         #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("g, grid", [(1, (1, 1)), (2, (1, 2)), (4, (2, 2)),
                                     (6, (2, 3)), (7, (1, 7)), (8, (2, 4)),
                                     (9, (3, 3))])
def test_device_grid_is_as_square_as_the_count_allows(g, grid):
    assert device_grid(g) == grid


def test_advice_grid_is_the_accelerators_in_index_order(ctx4):
    devs = _accel(ctx4)
    assert advice_grid(ctx4) == [devs[:2], devs[2:]]


@pytest.fixture
def placed(monkeypatch):
    """(class name, written tile's key) -> the devices its tasks were
    handed to, recorded at ``kernel_scheduler``."""
    seen = {}
    submit = JaxDevice.kernel_scheduler

    def recording(self, es, task):
        for flow in task.task_class.flows:
            if task.access_of(flow) & FlowAccess.WRITE:
                data = task.data[flow.flow_index].data_in.data
                seen.setdefault(data.key, []).append(self.device_index)
        return submit(self, es, task)

    monkeypatch.setattr(JaxDevice, "kernel_scheduler", recording)
    return seen


def test_on_four_devices_every_count_is_the_advice(ctx4, placed):
    devs = _accel(ctx4)
    inputs = gemm.make_input(N, 7)
    A, B, C = _operands(inputs)
    keys = ("tasks", "stage_out_bytes", "stage_in_peer_bytes") + PLACED_BY
    before = [{k: d.stats[k] for k in keys} for d in devs]
    phases.clear_completed()
    ops.pdgemm_dtd(ctx4, A, B, C, alpha=gemm.ALPHA, beta=gemm.BETA)
    moved = [{k: d.stats[k] - b[k] for k in keys}
             for d, b in zip(devs, before)]
    total = {k: sum(m[k] for m in moved) for k in keys}
    assert total["placed_by_advice"] == MT * NT
    assert total["placed_by_owner"] == MT * NT * (KT - 1)
    assert total["placed_by_load"] == 0
    assert [m["tasks"] for m in moved] == [MT * NT * KT // 4] * 4
    # every GEMM of C(m, n) on chip 2 (m mod 2) + (n mod 2)
    assert placed == {
        C.data_of(m, n).key: [devs[2 * (m % 2) + n % 2].device_index] * KT
        for m in range(MT) for n in range(NT)}
    # the advice is on the tiles of all three
    for M in (A, B, C):
        for (m, n) in M.tiles():
            assert M.data_of(m, n).preferred_device \
                == devs[2 * (m % 2) + n % 2].device_index
    # C came home and nothing else did
    assert total["stage_out_bytes"] == MT * NT * TILE_BYTES
    assert [m["stage_out_bytes"] for m in moved] \
        == [MT * NT * TILE_BYTES // 4] * 4
    # the call's record says the same a device
    (record,) = phases.completed()
    assert record["op"] == "pdgemm_dtd"
    assert [e["placement"] for e in record["by_device"]
            if e["device"] != "cpu"] \
        == [{k: m[k] for k in phases.PLACEMENT_COUNTERS} for m in moved]


def test_one_device_advises_nothing_and_counts_no_rule():
    c = _context(1)
    try:
        inputs = gemm.make_input(N, 9)
        A, B, C = _operands(inputs)
        ops.pdgemm_dtd(c, A, B, C)
        (dev,) = _accel(c)
        assert dev.stats["tasks"] == MT * NT * KT
        assert [dev.stats[rule] for rule in PLACED_BY] == [0, 0, 0]
        assert all(M.data_of(m, n).preferred_device == -1
                   for M in (A, B, C) for (m, n) in M.tiles())
    finally:
        c.fini()


# --------------------------------------------------------------------- #
# the flush: C home, A and B where they are                             #
# --------------------------------------------------------------------- #
def test_c_comes_home_and_a_and_b_are_left_alone(ctx, monkeypatch):
    inputs = gemm.make_input(N, 11)
    A, B, C = _operands(inputs)
    pulled = []
    pull = JaxDevice.pull_to_host

    def recording(self, data):
        pulled.append(data.key)
        return pull(self, data)

    monkeypatch.setattr(JaxDevice, "pull_to_host", recording)
    (before,) = _stats(ctx, "stage_out_bytes")
    ops.pdgemm_dtd(ctx, A, B, C, alpha=gemm.ALPHA, beta=gemm.BETA)
    # C's host copy is the newest: reading it pulls nothing more
    for (m, n) in C.tiles():
        data = C.data_of(m, n)
        assert data.newest_copy().device_id == 0
        assert data.owner_device == 0
    assert sorted(pulled) == sorted(C.data_of(m, n).key
                                    for (m, n) in C.tiles())
    got = C.to_numpy()
    (after,) = _stats(ctx, "stage_out_bytes")
    assert after - before == MT * NT * TILE_BYTES
    assert gemm.residual(got, gemm.expected(inputs, 11)) <= LIMIT
    # A and B: bit-unchanged, never copied back, their host copies not
    # bumped under the device copies
    for M, name in ((A, "A"), (B, "B")):
        assert np.array_equal(M.to_numpy(), inputs[name])
        for (m, n) in M.tiles():
            data = M.data_of(m, n)
            assert data.get_copy(0).version == data.newest_version()
            on_chip = [cp for cp in data.copies() if cp.device_id != 0]
            assert on_chip and all(cp.version == data.newest_version()
                                   for cp in on_chip)
    assert _stats(ctx, "stage_out_bytes") == [after]


def test_a_second_product_stages_a_and_b_in_again_only_if_refilled(ctx):
    """A tile that was only read keeps its device copies current (no
    flush task bumped its host copy), so a second product over the same
    A and B stages in C alone."""
    inputs = gemm.make_input(N, 13)
    A, B, C = _operands(inputs)
    ops.pdgemm_dtd(ctx, A, B, C)
    (before,) = _stats(ctx, "stage_in_bytes")
    ops.pdgemm_dtd(ctx, A, B, C.from_numpy(inputs["C"]))
    (after,) = _stats(ctx, "stage_in_bytes")
    assert after - before == MT * NT * TILE_BYTES
    want = inputs["C"].astype(np.float64) \
        + inputs["A"].astype(np.float64) @ inputs["B"]
    assert np.abs(C.to_numpy() - want).max() <= 1e-4 * np.abs(want).max()


def test_flush_all_leaves_a_tile_that_was_only_read_alone(ctx):
    from parsec_tpu import dtd
    from parsec_tpu.dsl.dtd import INOUT, INPUT
    tp = dtd.taskpool_new("flush")
    ctx.add_taskpool(tp)
    ctx.start()
    read = tp.tile_of_array(np.ones((4, 4), np.float32))
    written = tp.tile_of_array(np.ones((4, 4), np.float32))

    def body(es, task):
        w, r = dtd.unpack_args(task)
        w += r

    tp.insert_task(body, (written, INOUT), (read, INPUT))
    inserted = tp._inserted
    tp.data_flush_all()
    assert tp._inserted == inserted + 1      # one flush: the written tile
    assert (written.flushed_at_seq, read.flushed_at_seq) == (2, 0)
    tp.data_flush_all()                      # and once
    assert tp._inserted == inserted + 1
    tp.wait()
    ctx.wait()
    assert np.array_equal(written.data.get_copy(0).payload,
                          np.full((4, 4), 2, np.float32))


# --------------------------------------------------------------------- #
# a task alone computes what it computes in a stacked call              #
# --------------------------------------------------------------------- #
def test_a_lone_task_takes_its_values_as_constants():
    """One worker and ``device_batch_max`` 1: every task dispatched
    alone, against the stacked calls of the module's context."""
    inputs = gemm.make_input(N, 17)
    with params.cmdline_override("device_batch_max", "1"):
        alone = _context(1, cores=1)
    stacked = _context(1, cores=1)
    try:
        out = []
        for c in (alone, stacked):
            A, B, C = _operands(inputs)
            before = _stats(c, "batched_tasks")
            ops.pdgemm_dtd(c, A, B, C, alpha=gemm.ALPHA, beta=gemm.BETA)
            out.append((C.to_numpy(), _stats(c, "batched_tasks")[0]
                        - before[0]))
        assert out[0][1] == 0 and out[1][1] > 0
        assert np.array_equal(out[0][0], out[1][0])
    finally:
        alone.fini()
        stacked.fini()


# --------------------------------------------------------------------- #
# the readers of what it adds, and where BENCHMARK.json lists them       #
# --------------------------------------------------------------------- #
CELL = "dgemm-dtd-4chip.n32768-nb2048"
CELL4 = "dpotrf-4chip.n16384-nb512"
#: reader -> (value on the canned window below, cells that list it)
READERS = {"placed_by_advice_per_call": (256.0, [CELL4, CELL]),
           "placed_by_owner_per_call": (3840.0, [CELL4, CELL]),
           "placed_by_load_per_call": (0.0, [CELL4, CELL]),
           "device_task_imbalance_pct": (12.5, [CELL4, CELL]),
           "stage_out_gb": (4.25, [CELL]),
           "busiest_chip_busy_s": (0.75, [CELL4, CELL])}


def _canned_window(tasks=(1024, 1152, 1024, 896)):
    """Two calls of a traced window: the counters summed over them, a
    record a call, the traced call's busy seconds a chip."""
    phases.clear_completed()
    walls = [2.0, 2.5]
    for i, wall in enumerate(walls):
        phases._completed.append({
            "op": "pdgemm_dtd", "id": i, "traced": bool(i), "t0_ns": 0,
            "t1_ns": int(0.95 * wall * 1e9),
            "by_device": [{"device": f"tpu:{j}",
                           "placement": {"tasks": n}}
                          for j, n in enumerate(tasks)]})
    return {"walls": walls, "n_counted": 2, "n_traced": 1,
            "counters": {"placed_by_advice": 512, "placed_by_owner": 7680,
                         "placed_by_load": 0, "stage_out_bytes": 8.5e9},
            "trace": {"busy_by_chip_s": {0: 0.5, 1: 0.75, 2: 0.25, 3: 0.5}}}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_its_number_and_nothing_where_there_is_none(name):
    read = spec.metric_reader(name).read
    try:
        assert read(_canned_window()) == pytest.approx(READERS[name][0])
        # the parent: no such counter, no placement in a record, no trace
        bare = dict(_canned_window(), counters={}, trace=None)
        for rec in phases._completed:
            for entry in rec["by_device"]:
                del entry["placement"]
        assert read(bare) is None
        assert read(dict(bare, walls=[], n_counted=0, n_traced=0)) is None
    finally:
        phases.clear_completed()


def test_benchmark_lists_the_cell_and_its_readers():
    bench = spec.load_benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) == 10 and len(cells) >= 11  # later PRs append
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4][:2] \
        == [CELL4, CELL]            # later PRs append theirs
    assert bench["configs"][8]["name"] == "dgemm-dtd-f32-4chip"
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in READERS}
    names = list(per_layer)
    at = names.index("placed_by_advice_per_call")   # later PRs append theirs
    assert names[at:at + len(READERS)] == [
        "placed_by_advice_per_call", "placed_by_owner_per_call",
        "placed_by_load_per_call", "device_task_imbalance_pct",
        "stage_out_gb", "busiest_chip_busy_s"]
    for name, (_value, listed) in READERS.items():
        m = per_layer[name]
        assert m["workloads"][:len(listed)] == listed   # later PRs append
        assert m["moves"] == "factor_s"
        assert m["layer"] in layers
    # what the cell reports of what was there: the front end's spans,
    # its one class, and everything every cell reports
    reported = {m["name"] for m in spec.Cell(bench, CELL).metrics["per_layer"]}
    assert {"dtd_insert_s", "dtd_flush_s", "gemm_device_s", "stage_in_gb",
            "stage_in_peer_gb", "tiles_per_transfer", "tasks_per_call",
            "tile_kernels_roofline", "untraced_chip_wait_s",
            "compiles_in_window", "peak_hbm_gb", "device_idle_pct"} \
        <= reported
    # never held by the window (4,352 tasks under 8,000): nothing to read
    assert "dtd_window_s" not in reported


# --------------------------------------------------------------------- #
# the cell's files, its check and the check's control, collected from   #
# perfbench/checks/test_dgemm_dtd.py (tier-1 runs ``tests/`` only)       #
# --------------------------------------------------------------------- #
from perfbench.checks.test_dgemm_dtd import *  # noqa: E402,F401,F403
