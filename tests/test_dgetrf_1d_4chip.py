"""``ops.dgetrf_1d`` over several accelerators: HPL's 1 x Q layout.  On a
context with four accelerators the entry point advises block column n to
accelerator n mod 4, so every writer of a column runs on one chip, the
columns go round the chips, and a panel reaches each other chip once,
pulled whole by that chip's stage-in.  Counts and values only, on the
virtual devices of the CPU backend: no time is asserted.
"""
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import ops
from parsec_tpu.collections import BlockColumnCyclic
from parsec_tpu.data.data import FlowAccess
from parsec_tpu.devices.device import PLACED_BY
from parsec_tpu.devices.tpu import JaxDevice
from parsec_tpu.ops.linalg import PIV_ROWS
from parsec_tpu.utils.params import params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.reference import lu  # noqa: E402

NB, G, SEED = 32, 4, 2 ** 31 + 11
NEW_COUNTERS = ("peer_pulls", "peer_pull_ns", "stage_in_peer_bytes") \
    + PLACED_BY
with open(os.path.join(ROOT, "perfbench", "configs",
                       "dgetrf-f32-4chip.json")) as _f:
    LIMIT = json.load(_f)["check"]["limit"]
EPS = float(np.finfo(np.float32).eps)


def _accel(ctx):
    return [d for d in ctx.devices if d.device_type == "tpu"]


def _columns(M):
    return BlockColumnCyclic(*M.shape, NB, NB, dtype=np.float32)


def _call(ctx, A, M):
    """One call on the refilled matrix: (ipiv, what every accelerator's
    counters moved by)."""
    devs = _accel(ctx)
    A.from_numpy(M)
    before = [dict(d.stats) for d in devs]
    ipiv = np.asarray(ops.dgetrf_1d(ctx, A))
    return ipiv, [{k: d.stats[k] - b[k] for k in b} for d, b in
                  zip(devs, before)]


def _record_writers(mp, columns, position):
    """``{column: {accelerator positions its writers ran on}}``, filled
    where a task is handed to an accelerator; ``columns`` maps a block
    column's ``Data`` key to its index, ``position`` a device index to
    the accelerator's place among the context's."""
    seen = {n: set() for n in columns.values()}
    submit = JaxDevice.kernel_scheduler

    def recording(self, es, task):
        for flow in task.task_class.flows:
            if flow.ctl or not task.access_of(flow) & FlowAccess.WRITE:
                continue
            din = task.data[flow.flow_index].data_in
            if din is not None and din.data is not None \
                    and din.data.key in columns:
                seen[columns[din.data.key]].add(position[self.device_index])
        return submit(self, es, task)

    mp.setattr(JaxDevice, "kernel_scheduler", recording)
    return seen


def _four(nt, advise=None):
    """Two calls of ``ops.dgetrf_1d`` over one refilled matrix on a
    context of four accelerators; ``advise(devs, A)`` runs first."""
    M = lu.make_input(nt * NB, SEED)
    with params.cmdline_override("device_tpu_max", str(G)):
        ctx = parsec_tpu.init(nb_cores=4)
    try:
        devs = _accel(ctx)
        assert len(devs) == G
        A = _columns(M)
        if advise is not None:
            advise(devs, A)
        with pytest.MonkeyPatch.context() as mp:
            writers = _record_writers(
                mp, {A.data_of(0, n).key: n for n in range(nt)},
                {d.device_index: i for i, d in enumerate(devs)})
            ipiv, first = _call(ctx, A, M)
            factor = A.to_numpy()
            _ipiv2, second = _call(ctx, A, M)
        return SimpleNamespace(
            nt=nt, M=M, factor=factor, ipiv=ipiv, first=first,
            second=second, writers=writers,
            names=[d.name for d in devs],
            record=parsec_tpu.obs.phases.completed()[-1])
    finally:
        ctx.fini()


@pytest.fixture(scope="module", params=[8, 40])
def four(request):
    return _four(request.param)


@pytest.fixture(scope="module")
def one(four):
    """The same matrix factored on ONE accelerator, for every ``four``."""
    with params.cmdline_override("device_tpu_max", "1"):
        ctx = parsec_tpu.init(nb_cores=4)
    try:
        A = _columns(four.M)
        built = []
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(JaxDevice, "_build_ahead",
                       lambda self, *a: built.append(a))
            ipiv, moved = _call(ctx, A, four.M)
        return SimpleNamespace(
            factor=A.to_numpy(), ipiv=ipiv, moved=moved, built=built,
            advised=[A.data_of(0, n).preferred_device
                     for n in range(four.nt)])
    finally:
        ctx.fini()


def _tasks_of(nt, c):
    """PANEL(n), UPDATE(k, n) for k < n and LASWP(n) of the columns n
    with n mod G = c."""
    return sum(1 + n + (n < nt - 1) for n in range(c, nt, G))


def _pulls_of(nt, c):
    """(block columns, pivot tiles of a collection, runtime-made pivot
    tiles) chip c pulls from the others in one call: a panel k once if
    one of c's columns lies right of it; the LAST panel's pivot tile
    (``descP(0, 1)``) once for c's LASWPs; a panel's pivot tile once a
    consumer task (it is nobody's ``Data``: no copy of it is kept):
    UPDATE(k, n) of c's columns and PANEL(k + 1) on c."""
    mine = range(c, nt, G)
    panels = {k for n in mine for k in range(n) if k % G != c}
    last = int((nt - 1) % G != c and any(n < nt - 1 for n in mine))
    loose = sum(1 for n in mine for k in range(n) if k % G != c) \
        + sum(1 for n in mine if n > 0)
    return len(panels), last, loose


# ---- the layout ---------------------------------------------------------
def test_every_writer_of_column_n_ran_on_accelerator_n_mod_4(four):
    assert four.writers == {n: {n % G} for n in range(four.nt)}


def test_tasks_a_device_are_the_cyclic_count(four):
    nt = four.nt
    assert [d["tasks"] for d in four.first] \
        == [_tasks_of(nt, c) for c in range(G)]
    assert sum(d["tasks"] for d in four.first) \
        == nt + nt * (nt - 1) // 2 + nt - 1


def test_first_touches_go_by_advice_and_none_by_load(four):
    total = {rule: sum(d[rule] for d in four.first) for rule in PLACED_BY}
    tasks = sum(d["tasks"] for d in four.first)
    assert total == {"placed_by_advice": four.nt, "placed_by_load": 0,
                     "placed_by_owner": tasks - four.nt}
    assert [d["placed_by_advice"] for d in four.first] \
        == [len(range(c, four.nt, G)) for c in range(G)]


# ---- what crosses between the chips -------------------------------------
def test_each_chip_pulls_each_foreign_panel_it_reads_once(four):
    nt = four.nt
    column, pivots = nt * NB * NB * 4, PIV_ROWS * nt * NB * 4
    for c, moved in enumerate(four.first):
        panels, last, loose = _pulls_of(nt, c)
        assert moved["stage_in_peer_bytes"] \
            == panels * column + last * pivots, c
        assert moved["peer_pulls"] == panels + last + loose, c
        assert moved["peer_pull_ns"] > 0
        # the matrix itself comes from the host, each column once
        assert moved["stage_in_bytes"] - moved["stage_in_peer_bytes"] \
            >= len(range(c, nt, G)) * column
        assert moved["evictions"] == 0


def test_the_call_record_holds_the_peer_block(four):
    by_device = four.record["by_device"]
    assert len(by_device) == G
    for entry, moved in zip(by_device, four.second):
        assert entry["peer"] == {k: moved[k] for k in
                                 parsec_tpu.obs.phases.PEER_COUNTERS}
    report = parsec_tpu.obs.phases.format_report(four.record)
    assert f"peer pulls: {sum(d['peer_pulls'] for d in four.second)} " \
        in report


def test_a_second_call_places_and_pulls_the_same(four):
    keys = ("tasks", "peer_pulls", "stage_in_peer_bytes") + PLACED_BY
    assert [{k: d[k] for k in keys} for d in four.second] \
        == [{k: d[k] for k in keys} for d in four.first]


# ---- the programs: all of them in the first call ------------------------
def _programs_of(nt):
    """{name: devices that called it} of the stacked programs whose
    tasks take a block column of ``nt`` tiles."""
    from parsec_tpu.devices import batching
    column = (nt * NB, NB)
    return {fn.name: set(fn._called_on)
            for cache in batching._shared_cache.values()
            for key, fn in cache.items()
            if any(shape == column for shape, _dtype in key[3])}


def test_every_chip_built_every_bucket_its_share_of_columns_can_form(four):
    from parsec_tpu.devices.batching import bucket_size
    top = bucket_size(-(-four.nt // G), 16)
    programs = _programs_of(four.nt)
    b = 2
    while b <= top:
        for cls in ("UPDATE", "LASWP"):
            assert programs[f"{cls}_x{b}"] >= set(four.names), (cls, b)
        b *= 2
    # a panel is alone on its chip, and a chip never holds more of a
    # class than its columns
    assert not [n for n in programs if n.startswith("PANEL_x")
                or int(n.rsplit("_x", 1)[1]) > top]


def test_a_second_call_builds_no_program(four):
    assert [d["first_calls"] for d in four.second] == [0] * G
    assert [d["batch_downgrades"] for d in four.second] == [0] * G


def test_one_accelerator_builds_nothing_ahead(one):
    assert one.built == []


# ---- the factor ---------------------------------------------------------
def test_factor_passes_the_check_with_every_multiplier_at_most_one(four):
    assert np.abs(np.tril(four.factor, -1)).max() <= 1.0
    assert lu.residual(four.factor, lu.expected(four.M, SEED)) <= LIMIT


def test_factor_is_the_one_device_factor_and_plain_numpy_lu(four, one):
    """The same pivots as one accelerator and as the float64 reference
    (``reference/lu.py: plain_factor``, numpy, partial pivoting); the
    factors differ by float32 rounding of each entry's history, bounded
    by n eps |L||U| (``tests/test_dgetrf_1d.py``)."""
    ref, ref_ipiv = lu.plain_factor(four.M.astype(np.float64), NB,
                                    with_pivots=True)
    assert np.array_equal(four.ipiv, one.ipiv)
    assert np.array_equal(four.ipiv, ref_ipiv)
    n = four.M.shape[0]
    scale = n * EPS * (np.abs(np.tril(ref, -1)) @ np.abs(np.triu(ref))
                       + np.abs(np.triu(ref))).max()
    assert np.abs(four.factor - ref).max() <= scale
    assert np.abs(four.factor - one.factor).max() <= scale


# ---- what decides: the caller first, one accelerator nothing ------------
def test_one_accelerator_advises_nothing_and_counts_nothing(four, one):
    assert one.advised == [-1] * four.nt
    assert len(one.moved) == 1
    assert {k: one.moved[0][k] for k in NEW_COUNTERS} \
        == dict.fromkeys(NEW_COUNTERS, 0)


@pytest.mark.parametrize("nt", [8])
def test_a_callers_own_advice_wins(nt):
    """Column 1 advised to the LAST accelerator and column 2 to the
    first by the caller: they stay there, the others go round."""
    def advise(devs, A):
        devs[-1].data_advise(A.data_of(0, 1), "preferred_device")
        devs[0].data_advise(A.data_of(0, 2), "preferred_device")

    got = _four(nt, advise)
    want = {n: {n % G} for n in range(nt)}
    want[1], want[2] = {G - 1}, {0}
    assert got.writers == want
    assert sum(d["placed_by_advice"] for d in got.first) == nt
    assert sum(d["placed_by_load"] for d in got.first) == 0
    assert lu.residual(got.factor, lu.expected(got.M, SEED)) <= LIMIT
