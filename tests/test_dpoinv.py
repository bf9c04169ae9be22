"""``ops.dpoinv`` / ``ops.dpotri``: the inverse of an SPD matrix as three
composed tile DAGs through the runtime, held against float64
``numpy.linalg.inv``, the closed form no factorization touches and the
plain reference (``perfbench/reference/poinv.py``); and what it forced:
composition over one collection, twelve classes on the stacked dispatch,
writes after reads stated as CTL flows.  Counts and structure only: no
time is asserted.
"""
import json
import os
import sys

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import ops
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.devices import batching
from parsec_tpu.devices.tpu import JaxDevice
from parsec_tpu.obs import phases
from parsec_tpu.runtime.compound import CompoundTaskpool
from parsec_tpu.runtime.context import Context
from parsec_tpu.utils.params import params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import spec  # noqa: E402
from perfbench.reference import poinv  # noqa: E402

NB = 64
PARTS = {"dpotrf_L": ("POTRF", "TRSM", "SYRK", "GEMM"),
         "dtrtri_L": ("TRTRI", "TRSMR", "TRSML", "GEMMI"),
         "dlauum_L": ("LAUUM", "TRMM", "SYRKT", "GEMMT")}
CLASSES = {c for part in PARTS.values() for c in part}
with open(os.path.join(ROOT, "perfbench", "configs",
                       "dpoinv-f32-1chip.json")) as _f:
    LIMIT = json.load(_f)["check"]["limit"]
EPS = float(np.finfo(np.float32).eps)
dpoinv_module = sys.modules["parsec_tpu.ops.dpoinv"]


def _tiled(M, nb=NB):
    n = M.shape[0]
    return TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)


def _stat(ctx, key):
    return sum(d.stats[key] for d in ctx.devices if d.device_type == "tpu")


def _n_part(nt):
    return nt * (nt + 1) * (nt + 2) // 6


def _lower_error(R, want):
    """max |tril(R) - tril(want)| over max |want|."""
    return np.abs(np.tril(R) - np.tril(want)).max() / np.abs(want).max()


def _lower_triangular(n, seed):
    """A well-conditioned lower triangular matrix (float32)."""
    rng = np.random.default_rng(seed)
    L = np.tril(rng.random((n, n), dtype=np.float32) - np.float32(0.5))
    L /= np.float32(n ** 0.5)
    L[np.diag_indices(n)] = 1.0 + rng.random(n, dtype=np.float32)
    return L


@pytest.fixture(scope="module")
def ctx():
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=4)
    yield c
    c.fini()


@pytest.fixture(scope="module")
def one_at_a_time():
    with params.cmdline_override("device_tpu_max", "1"), \
            params.cmdline_override("device_batch_max", "1"):
        c = parsec_tpu.init(nb_cores=4)
    yield c
    c.fini()


@pytest.fixture
def dispatched(monkeypatch):
    """(class, locals) of every task in the order the device module
    filed its call."""
    order = []
    filed = JaxDevice._finish_submit

    def recording(self, es, rec):
        order.extend((t.task_class.name, t.locals) for t in rec.tasks)
        return filed(self, es, rec)

    monkeypatch.setattr(JaxDevice, "_finish_submit", recording)
    return order


# ---- the tile kernels against numpy --------------------------------------
@pytest.mark.parametrize("kernel,args,want", [
    ("trtri_lower", "t", lambda t, c, a: np.linalg.inv(np.tril(t))),
    ("trsm_lower_right_neg", "tc",
     lambda t, c, a: -c @ np.linalg.inv(np.tril(t))),
    ("trmm_lower_trans", "tc", lambda t, c, a: np.tril(t).T @ c),
    ("lauum_lower", "t", lambda t, c, a: np.tril(t).T @ np.tril(t)),
    ("syrk_lt", "ca", lambda t, c, a: c + a.T @ a),
    ("gemm_tn", "cat", lambda t, c, a: c + a.T @ t),
])
def test_tile_kernel_is_what_it_says(kernel, args, want):
    """Each new kernel of ``ops/linalg.py`` against float64 numpy; the
    diagonal tile carries junk above its diagonal, which a triangular
    kernel must not read."""
    rng = np.random.default_rng(7)
    t = _lower_triangular(48, 1) + np.triu(
        rng.random((48, 48), dtype=np.float32), 1)
    c = rng.random((48, 48), dtype=np.float32) - np.float32(0.5)
    a = rng.random((48, 48), dtype=np.float32) - np.float32(0.5)
    given = {"t": t, "c": c, "a": a}
    got = np.asarray(getattr(ops, kernel)(*[given[x] for x in args]))
    ref = want(*[x.astype(np.float64) for x in (t, c, a)])
    assert np.abs(got - ref).max() <= 64 * EPS * max(1.0, np.abs(ref).max())


# ---- the inverse against float64 and the closed form ---------------------
@pytest.mark.parametrize("nt", [1, 2, 3, 5, 8])
def test_dpoinv_is_the_inverse(ctx, nt):
    """The lower tiles hold the lower triangle of the float64 inverse of
    the input, and of the closed form (I + WW')^-1 = I - W (I + W'W)^-1
    W' that no factorization touches; a diagonal tile is the whole
    symmetric block; the tiles above the diagonal keep their bits;
    every task of the three DAGs ran on the accelerator.

    Tolerance: the inverse of a matrix of condition number c computed
    in float32 is off by about c eps relative to its largest entry
    (c = n / 64 + 1 <= 9 here); these read 3e-7 to 5e-7, and 32 eps is
    3.8e-6.  The closed form inverts I + WW' before ``make_input``
    rounds it to float32, which moves the inverse by up to c eps too."""
    n = nt * NB
    seed = 2 ** 31 + nt
    M = poinv.make_input(n, seed)
    A = _tiled(M)
    before = _stat(ctx, "tasks")
    ops.dpoinv(ctx, A)
    assert _stat(ctx, "tasks") - before == 3 * _n_part(nt)
    R = A.to_numpy()
    inv = np.linalg.inv(M.astype(np.float64))
    assert _lower_error(R, inv) <= 32 * EPS
    assert _lower_error(R, poinv.closed_form(n, seed)) <= 32 * EPS
    assert poinv.residual(R, poinv.expected(M, seed)) <= LIMIT
    for k in range(nt):     # a diagonal tile: both triangles
        d = slice(k * NB, (k + 1) * NB)
        assert np.abs(R[d, d] - inv[d, d]).max() <= 32 * EPS * np.abs(inv).max()
    above = np.triu(np.ones((nt, nt), bool), 1).repeat(NB, 0).repeat(NB, 1)
    assert np.array_equal(R[above], M[above])


@pytest.mark.parametrize("nt", [1, 2, 3, 5, 8])
def test_class_counts_are_the_kernel_files(nt):
    """Each class of each part has as many instances as its file under
    ``perfbench/kernels`` says, and each part NT (NT+1) (NT+2) / 6."""
    A = TwoDimBlockCyclic(nt * NB, nt * NB, NB, NB, dtype=np.float32)
    pools = {"dpotrf_L": ops.dpotrf_taskpool(A),
             "dtrtri_L": ops.dtrtri_taskpool(A),
             "dlauum_L": ops.dlauum_taskpool(A)}
    for name, classes in PARTS.items():
        assert pools[name].name == name
        counts = {c: sum(1 for _ in pools[name].class_by_name(c).iter_space())
                  for c in classes}
        for c in classes:
            formula = spec.data_file("kernels", f"dpoinv.{c}")["count"]
            assert counts[c] == spec.formula(formula, {"NT": nt}), c
        assert sum(counts.values()) == _n_part(nt)


@pytest.mark.parametrize("nt", [2, 5])
def test_dpotri_after_dpotrf_is_dpoinv(ctx, nt):
    """``ops.dpotri`` on dpotrf's factor: DPLASMA's two calls give what
    the one call gives."""
    n = nt * NB
    M = poinv.make_input(n, 40 + nt)
    A = _tiled(M)
    ops.dpotrf(ctx, A)
    before = _stat(ctx, "tasks")
    ops.dpotri(ctx, A)
    assert _stat(ctx, "tasks") - before == 2 * _n_part(nt)
    inv = np.linalg.inv(M.astype(np.float64))
    assert _lower_error(A.to_numpy(), inv) <= 32 * EPS
    assert _lower_error(A.to_numpy(), poinv.closed_form(n, 40 + nt)) \
        <= 32 * EPS


@pytest.mark.parametrize("nt", [1, 4, 7])
def test_dtrtri_alone(ctx, nt):
    """``L <- L^-1`` on a lower triangular matrix whose upper tiles hold
    junk that stays."""
    n = nt * NB
    L = _lower_triangular(n, nt)
    M = L + np.triu(np.full((n, n), 7.0, np.float32), NB)
    A = _tiled(M)
    ops.dtrtri(ctx, A)
    R = A.to_numpy()
    want = np.linalg.inv(L.astype(np.float64))
    assert _lower_error(R, want) <= 32 * EPS
    assert np.array_equal(np.triu(R, NB), np.triu(M, NB))


@pytest.mark.parametrize("nt", [1, 4, 7])
def test_dlauum_alone(ctx, nt):
    """``L <- L' L``, lower tiles."""
    n = nt * NB
    L = _lower_triangular(n, 10 + nt)
    A = _tiled(L)
    ops.dlauum(ctx, A)
    want = L.astype(np.float64).T @ L.astype(np.float64)
    assert _lower_error(A.to_numpy(), want) <= 32 * EPS


def test_refuses_a_grid_that_is_not_square(ctx):
    T = TwoDimBlockCyclic(128, 64, NB, NB, dtype=np.float32)
    with pytest.raises(ValueError, match="square"):
        ops.dpotri(ctx, T)


# ---- the path it takes ---------------------------------------------------
def test_one_add_taskpool_three_parts_in_order(monkeypatch):
    """The caller adds ONE taskpool, a compound; its three parts are
    enqueued one after the other, each from the completion of the one
    before; the device counts three parts and the call's record has
    them: enqueued <= first device call <= completed, in order."""
    added = []
    add = Context.add_taskpool

    def recording(self, tp):
        added.append(tp)
        return add(self, tp)

    monkeypatch.setattr(Context, "add_taskpool", recording)
    phases.clear_completed()
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=2, profile=True)
    try:
        M = poinv.make_input(4 * NB, 3)
        before = _stat(c, "compound_parts")
        ops.dpoinv(c, _tiled(M))
        assert _stat(c, "compound_parts") - before == 3
        assert [type(tp) is CompoundTaskpool for tp in added] \
            == [True, False, False, False]
        assert [tp.name for tp in added[1:]] == list(PARTS)
        rec, = phases.completed()
        assert rec["op"] == "dpoinv"
        assert [p["name"] for p in rec["parts"]] == list(PARTS)
        at = rec["t0_ns"]
        for p in rec["parts"]:
            assert at <= p["enqueued_ns"] <= p["first_call_ns"] \
                <= p["completed_ns"] <= rec["t1_ns"]
            at = p["completed_ns"]
        gaps = [b["first_call_ns"] - a["completed_ns"]
                for a, b in zip(rec["parts"], rec["parts"][1:])]
        assert rec["compound_gap_ns"] == sum(gaps) > 0
        assert rec["phases"]["complete"]["count"] == 3 * _n_part(4)
        report = phases.format_report(rec)
        assert "part 1 dtrtri_L" in report and "compound_gap" in report
    finally:
        c.fini()
        phases.clear_completed()


def test_a_call_that_composes_nothing_has_no_parts():
    phases.clear_completed()
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=2, profile=True)
    try:
        before = _stat(c, "compound_parts")
        ops.dpotrf(c, _tiled(poinv.make_input(2 * NB, 3)))
        rec, = phases.completed()
        assert "parts" not in rec and "compound_gap_ns" not in rec
        assert _stat(c, "compound_parts") == before
    finally:
        c.fini()
        phases.clear_completed()


@pytest.mark.parametrize("entry,parts", [("dpoinv", 3), ("dpotri", 2)])
def test_stage_in_is_one_pass_over_the_lower_tiles(ctx, entry, parts):
    """A part finds its tiles where the part before left them: the whole
    call stages in each lower tile once, not once a part, and stages
    nothing out."""
    nt = 6
    M = poinv.make_input(nt * NB, 9)
    if entry == "dpotri":
        M = np.tril(np.linalg.cholesky(M.astype(np.float64))).astype(
            np.float32)
    A = _tiled(M)
    keys = ("stage_in_bytes", "stage_out_bytes", "compound_parts")
    before = [_stat(ctx, k) for k in keys]
    getattr(ops, entry)(ctx, A)
    staged, out, composed = (
        _stat(ctx, k) - b for k, b in zip(keys, before))
    assert staged == nt * (nt + 1) // 2 * NB * NB * 4
    assert out == 0
    assert composed == parts


def test_stacked_and_one_at_a_time_agree_to_the_bit(ctx, one_at_a_time,
                                                    call_sizes):
    """The same bits whether the tasks go out in stacked calls or each
    alone; the stacked run did stack every one of the twelve classes'
    kinds of call it met, and no fast rung gave way; a task dispatched
    alone runs ``jit_<CLASS>`` for all twelve classes."""
    M = poinv.make_input(8 * NB, 77)
    down = [_stat(ctx, k) for k in ("batch_downgrades", "donate_retries")]
    A = _tiled(M)
    ops.dpoinv(ctx, A)
    assert call_sizes and max(call_sizes) >= 4
    assert [_stat(ctx, k) for k in ("batch_downgrades",
                                    "donate_retries")] == down
    del call_sizes[:]
    A1 = _tiled(M)
    ops.dpoinv(one_at_a_time, A1)
    assert not call_sizes
    assert np.array_equal(A.to_numpy(), A1.to_numpy())
    assert CLASSES <= {name for (name, _fn) in batching._class_kernels}


@pytest.mark.parametrize("stacking", ["one_at_a_time", "stacked"])
def test_programs_held_do_not_depend_on_nt(ctx, one_at_a_time, no_programs,
                                           monkeypatch, stacking):
    """NT = 4 then NT = 8 at one tile shape no other test has: one task
    at a time the twelve classes hold twelve programs after either;
    stacked, every program is one of a class's few bucket sizes and
    NT = 8 adds no signature per step index."""
    monkeypatch.setattr(batching, "_class_kernels", {})
    c = ctx if stacking == "stacked" else one_at_a_time
    held = []
    for nt in (4, 8):
        M = poinv.make_input(nt * 24, nt)
        A = _tiled(M, nb=24)
        ops.dpoinv(c, A)
        assert _lower_error(A.to_numpy(),
                            np.linalg.inv(M.astype(np.float64))) <= 32 * EPS
        held.append(batching.programs_held(CLASSES))
    if stacking == "one_at_a_time":
        assert held == [12, 12]
    else:
        buckets = {f"{cls}_x{b}" for cls in CLASSES for b in (2, 4, 8, 16)}
        names = {fn.name for cache in batching._shared_cache.values()
                 for fn in cache.values()}
        assert names and names <= buckets
        # a program per step index would show as 4 + 8 signatures of a
        # class; a class holds at most its buckets (donated or not)
        # and its lone kernel
        assert all(batching.programs_held({cls}) <= 2 * 4 + 1
                   for cls in CLASSES)


# ---- writes after reads --------------------------------------------------


@pytest.mark.parametrize("nt", [4, 7])
def test_a_tile_is_overwritten_after_its_readers(ctx, dispatched, nt):
    """Both in-place algorithms read a tile and then overwrite it in the
    same step.  A task's inputs are its tiles' one device copy at
    stage-in, so the writer must be dispatched after every reader: the
    CTL gathers of the JDFs hold the order, for every tile."""
    M = poinv.make_input(nt * NB, 5)
    ops.dpoinv(ctx, _tiled(M))
    at = {task: i for i, task in enumerate(dispatched)}
    assert len(at) == len(dispatched) == 3 * _n_part(nt)

    def before(first, then):
        return at[first] < at[then]

    for k in range(nt):
        for n in range(k):
            # dtrtri: A(k,n) is B of GEMMI(k, *, n), then TRSML(k, n)
            for m in range(k + 1, nt):
                assert before(("GEMMI", (k, m, n)), ("TRSML", (k, n)))
            assert before(("TRSML", (k, n)), ("TRTRI", (k,)))
            # dlauum: A(k,n) is read by the step's products, then TRMM
            assert before(("SYRKT", (k, n)), ("TRMM", (k, n)))
            for m in range(n + 1, k):
                assert before(("GEMMT", (k, m, n)), ("TRMM", (k, n)))
                assert before(("GEMMT", (k, m, n)), ("TRMM", (k, m)))
            assert before(("TRMM", (k, n)), ("LAUUM", (k,)))
        for m in range(k + 1, nt):
            assert before(("TRSMR", (k, m)), ("TRTRI", (k,)))
            # dtrtri: A(m,k) is A of GEMMI(k, m, *), then its next update
            nxt = ("TRSML", (m, k)) if m == k + 1 else ("GEMMI", (k + 1, m, k))
            for n in range(k):
                assert before(("GEMMI", (k, m, n)), nxt)


def _without_ctl(jdf):
    out, skipping = [], False
    for line in jdf.splitlines():
        if line.startswith("CTL"):
            skipping = True
            continue
        if skipping and line.startswith("       ") \
                and ("<-" in line or "->" in line):
            continue
        skipping = False
        out.append(line)
    return "\n".join(out)


def test_without_the_ctl_flows_a_reader_sees_the_overwritten_tile(
        ctx, monkeypatch):
    """The same JDFs less their CTL flows: every true dependence is
    still there, and the inverse comes out wrong, because a flow does
    not carry the value its producer made."""
    monkeypatch.setattr(dpoinv_module, "DTRTRI_L_JDF",
                        _without_ctl(dpoinv_module.DTRTRI_L_JDF))
    monkeypatch.setattr(dpoinv_module, "DLAUUM_L_JDF",
                        _without_ctl(dpoinv_module.DLAUUM_L_JDF))
    monkeypatch.setattr(dpoinv_module, "_factories", {})
    M = poinv.make_input(8 * NB, 13)
    A = _tiled(M)
    ops.dpoinv(ctx, A)
    assert not poinv.residual(A.to_numpy(), poinv.expected(M, 13)) <= LIMIT


# ---- the comparison that decides `correct`, and its controls -------------
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11, 77])
def test_reference_misses_the_limit_below_its_precision(seed):
    """The plain reference in the program's place passes at the
    configuration's precision and misses the configuration's limit one
    precision below ('high': 16 bits) and two ('default': 8 bits)."""
    M = poinv.make_input(2048, seed)
    exp = poinv.expected(M, seed)
    sound = poinv.residual(poinv.plain_factor(M, 128, "highest"), exp)
    high = poinv.residual(poinv.plain_factor(M, 128, "high"), exp)
    low = poinv.residual(poinv.plain_factor(M, 128, "default"), exp)
    assert sound <= LIMIT < high < low


def test_reference_passes_the_float64_inverse_and_the_closed_form():
    M = poinv.make_input(512, 21)
    exp = poinv.expected(M, 21)
    assert poinv.residual(np.linalg.inv(M.astype(np.float64)), exp) < 1e-12
    assert poinv.residual(poinv.closed_form(512, 21), exp) <= LIMIT
    W = poinv.seeded_w(512, 21)
    assert np.abs(np.eye(512) + W @ W.T - M).max() <= 4 * EPS * 2.0


def test_reference_reads_the_lower_triangle_alone():
    """The check is handed the tiled result: what lies above the
    diagonal (the input's tiles) is not read."""
    M = poinv.make_input(256, 4)
    exp = poinv.expected(M, 4)
    inv = np.linalg.inv(M.astype(np.float64))
    a = poinv.residual(inv, exp)
    b = poinv.residual(np.tril(inv) + np.triu(M, 1), exp)
    assert a == b


@pytest.mark.parametrize("kernel,broken", [
    ("trmm_lower_trans", lambda t, c: c),       # TRMM left out
    ("trtri_lower", lambda t: t),               # TRTRI returns its input
    ("gemm_nn", lambda c, a, b: c),             # GEMMI left out
])
def test_a_broken_tile_kernel_is_not_correct(ctx, monkeypatch, kernel,
                                             broken):
    """One tile kernel of parts 2 or 3 returning its state unchanged
    underneath the entry point: the residual misses the limit (the twin
    of ``perfbench/checks/broken_kernel.py`` on the chip)."""
    M = poinv.make_input(4 * NB, 2 ** 31 + 5)
    exp = poinv.expected(M, 2 ** 31 + 5)
    A = _tiled(M)
    ops.dpoinv(ctx, A)
    assert poinv.residual(A.to_numpy(), exp) <= LIMIT
    monkeypatch.setattr(ops, kernel, broken)
    A = _tiled(M)
    ops.dpoinv(ctx, A)
    assert not poinv.residual(A.to_numpy(), exp) <= LIMIT
