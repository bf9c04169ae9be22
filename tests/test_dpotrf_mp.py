"""``ops.dpotrf_mp``: the three-precision band tile Cholesky through the
runtime, held against the plain reference
(``perfbench/reference/cholesky_mp.py``) by the band-wise number, and
what it forced: a task class a level, conversion on the flows made once
a (produced tile, target type) on the device and dropped with its last
reader.  Counts and structure only: no time is asserted.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import ops
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.data.data import Coherency, Data, DataCopy
from parsec_tpu.data.datatype import Datatype
from parsec_tpu.data.reshape import ReshapeRepo
from parsec_tpu.devices import batching
from parsec_tpu.obs import phases
from parsec_tpu.ops.dpotrf_mp import converted_tiles, dpotrf_mp_taskpool
from parsec_tpu.utils.params import params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import spec  # noqa: E402
from perfbench.reference import cholesky, cholesky_mp  # noqa: E402

CELL = "dpotrf-mp.n32768-nb2048"
BH, BM = 2, 5
SHAPES = [(1024, 128), (640, 64)]       # NT = 8, NT = 10
CLASSES = {"POTRF", "TRSM", "TRSM_MID", "SYRK", "GEMM", "GEMM_MID",
           "GEMM_LO"}
LIMITS = cholesky_mp.limits()


def _tiled(M, nb):
    n = M.shape[0]
    return TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)


def _accel(ctx):
    dev, = [d for d in ctx.devices if d.device_type == "tpu"]
    return dev


def _moved(dev, before):
    return {k: v - before[k] for k, v in dev.stats.items()
            if isinstance(v, (int, float))}


def _lo_edges(nt, bm):
    """Flows of GEMM_LO tasks that take a converted tile: two a task."""
    return 2 * sum(1 for k in range(nt - 2) for m in range(k + 2, nt)
                   for n in range(k + 1, m) if m - n >= bm)


@pytest.fixture(scope="module")
def ctx():
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=4)
    yield c
    c.fini()


# ---- the system against the plain reference ------------------------------
@pytest.mark.parametrize("n,nb", SHAPES)
def test_the_system_against_the_plain_reference_band_by_band(ctx, n, nb):
    """Seeded Matern input: each level's number of the system's factor
    and of ``plain_factor`` under the level's limit; the lo band, the
    same arithmetic in both (bf16 operands, f32 accumulation), reads
    alike; the number compared is under 1."""
    seed = 2 ** 31 + 7
    M = cholesky_mp.make_input(n, seed)
    exp = cholesky_mp.expected(M, seed, nb, BH, BM)
    A = _tiled(M, nb)
    ops.dpotrf_mp(ctx, A)
    got = cholesky_mp.level_numbers(A.to_numpy(), exp)
    want = cholesky_mp.level_numbers(
        cholesky_mp.plain_factor(M, nb, BH, BM), exp)
    for lv in cholesky_mp.LEVELS:
        assert 0 < got[lv] <= LIMITS[lv], (lv, got)
    # the limits are the cell's (N = 32768, NB = 2048, read on the chip):
    # the reference's 16-bit mid band reads up to 1.0e-5 on tiles this
    # small, where the chip reads 2.1e-6 at the cell's
    assert 0 < want["hi"] <= LIMITS["hi"] and want["lo"] <= LIMITS["lo"]
    assert 0 < want["mid"] <= 2 * LIMITS["mid"]
    assert 0.5 < got["lo"] / want["lo"] < 2.0
    # XLA's host backend computes an f32 product exactly whatever the
    # precision asked: mid is no worse here than the reference's 16 bits
    assert got["mid"] <= want["mid"] and got["hi"] < 4 * want["hi"]
    assert cholesky_mp.residual(A.to_numpy(), exp) <= 1.0
    assert A.to_numpy().dtype == np.float32


def test_the_input_is_the_seeds_and_symmetric_positive_definite():
    a, b = (cholesky_mp.make_input(512, 2 ** 31 + 5) for _ in range(2))
    assert a.dtype == np.float32 and np.array_equal(a, b)
    assert np.array_equal(a, a.T) and np.all(a.diagonal() == 1.0)
    assert not np.array_equal(a, cholesky_mp.make_input(512, 2 ** 31 + 6))
    assert np.linalg.eigvalsh(a.astype(np.float64)).min() > 1e-3
    # Morton order: neighbours in the order are neighbours in the plane
    xy = cholesky_mp.locations(512, 3)
    assert np.median(np.hypot(*(xy[1:] - xy[:-1]).T)) < 2.5 / 512 ** 0.5


@pytest.mark.parametrize("n,nb", [(512, 128), (640, 64)])
def test_bands_that_make_everything_hi_give_dpotrfs_factor_bit_for_bit(
        ctx, n, nb):
    M = cholesky_mp.make_input(n, 3)
    A, B = _tiled(M, nb), _tiled(M, nb)
    before = dict(_accel(ctx).stats)
    ops.dpotrf_mp(ctx, A, band_high=n // nb, band_mid=n // nb)
    assert _moved(_accel(ctx), before)["conversions"] == 0
    ops.dpotrf(ctx, B)
    assert np.array_equal(np.tril(A.to_numpy()), np.tril(B.to_numpy()))


# ---- conversion on the flows ---------------------------------------------
@pytest.mark.parametrize("n,nb", SHAPES)
def test_a_tile_is_converted_once_whatever_the_number_of_its_readers(
        ctx, n, nb):
    """The device's counter equals the TRSM outputs a lo GEMM reads,
    every further reader is a hit, nothing converted is staged in or
    left in the device's books, and the engine holds nothing after the
    call."""
    nt = n // nb
    dev = _accel(ctx)
    A = _tiled(cholesky_mp.make_input(n, 5), nb)
    tp = dpotrf_mp_taskpool(A, BH, BM)
    before, used = dict(dev.stats), dev.mem_used
    ops.blocking.run_blocking(ctx, "dpotrf_mp", [tp])
    moved = _moved(dev, before)
    want = converted_tiles(nt, BM)
    assert want > 0 and moved["conversions"] == want
    assert moved["conversion_bytes"] == want * nb * nb * 2
    assert moved["reshape_hits"] == _lo_edges(nt, BM) - want
    assert moved["reshape_n"] == _lo_edges(nt, BM)
    repo = tp.reshape_repo
    assert repo.stats["conversions"] == repo.stats["released"] == want
    assert repo.held() == 0 and not repo._uses and not repo._counted
    # the lower triangle went to the chip and nothing else: a converted
    # tile is made there and never staged
    lower = nt * (nt + 1) // 2
    assert moved["stage_in_tiles"] == lower
    assert moved["stage_in_bytes"] == lower * nb * nb * 4
    assert moved["stage_in_peer_bytes"] == moved["stage_out_bytes"] == 0
    assert dev.mem_used - used == lower * nb * nb * 4
    assert moved["tasks"] == nt * (nt + 1) * (nt + 2) // 6
    assert moved["batch_downgrades"] == 0


def test_the_count_of_converted_tiles():
    assert converted_tiles(16, 5) == 95     # the cell
    assert converted_tiles(8, 5) == 6 and converted_tiles(4, 5) == 0
    assert converted_tiles(16, 16) == 0
    # every TRSM output below the first tile row but the last column's
    assert converted_tiles(6, 1) == sum(1 for k in range(5)
                                        for m in range(k + 1, 6)
                                        if m - k > 1 or m <= 4)


def test_a_counted_promise_lives_from_its_first_use_to_its_last():
    """``acquire`` / ``retain`` / ``release`` on a host tile: one
    conversion for any number of uses, the promise and the payload gone
    with the last, a new version of the same copy converted anew."""
    repo = ReshapeRepo()
    d = Data(nb_elts=16)
    src = DataCopy(d, 0, payload=np.arange(16, dtype=np.float32).reshape(4, 4))
    src.version, src.coherency = 1, Coherency.OWNED
    d.attach_copy(src)
    lo = Datatype(jnp.bfloat16, (4, 4))
    a = repo.acquire(src, lo)
    repo.retain(a)
    assert repo.acquire(src, lo) is a and repo.stats["conversions"] == 1
    assert a.payload.dtype == jnp.bfloat16 and repo.held() == 1
    repo.release(a)
    repo.release(a)
    assert repo.held() == 1 and a.payload is not None
    repo.release(a)
    assert repo.held() == 0 and a.payload is None
    assert repo.stats["released"] == 1
    repo.release(a)                     # not counted any more: nothing
    src.version = 2
    b = repo.acquire(src, lo)
    assert b is not a and repo.stats["conversions"] == 2
    # a copy that already has the type passes through, uncounted
    assert repo.acquire(b, lo) is b and repo.held() == 1
    # the uncounted form leaves its promise until clear()
    other = repo.reshaped_copy(src, Datatype(np.float16, (4, 4)))
    assert other.payload.dtype == np.float16 and repo.held() == 2
    repo.release(other)
    assert repo.held() == 2
    repo.clear()
    assert repo.held() == 0


def test_the_record_and_the_phase_of_the_reshape_pass():
    """Under ``Context(profile=True)`` the call's record books a
    ``reshape`` span for every flow that declares the type, and its
    ``by_device`` entry carries the engine's counters."""
    phases.clear_completed()
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=2, profile=True)
    try:
        ops.dpotrf_mp(c, _tiled(cholesky_mp.make_input(1024, 2), 128))
        rec, = phases.completed()
        assert rec["op"] == "dpotrf_mp"
        assert "reshape" in phases.PHASES
        assert rec["phases"]["reshape"]["count"] == _lo_edges(8, BM)
        assert rec["phases"]["reshape"]["self_ns"] > 0
        entry, = rec["by_device"]
        assert entry["reshape"]["conversions"] == converted_tiles(8, BM)
        assert entry["reshape"]["reshape_hits"] \
            == _lo_edges(8, BM) - converted_tiles(8, BM)
        assert entry["reshape"]["reshape_n"] == _lo_edges(8, BM)
        assert 0 < entry["reshape"]["reshape_ns"] \
            <= rec["t1_ns"] - rec["t0_ns"]
        assert "reshape" in phases.format_report(rec)
    finally:
        c.fini()
        phases.clear_completed()


# ---- a class a level: the tasks stack, no program is keyed by k, m, n ----
def test_levels_stack_and_programs_held_do_not_depend_on_nt(
        ctx, no_programs, call_sizes, monkeypatch):
    """NT = 4 and NT = 8 with bands (1, 2), so that both hold all three
    levels, at a tile no other test has: every program is one of a
    class's few bucket sizes with ONE compiled signature."""
    monkeypatch.setattr(batching, "_class_kernels", {})
    held = []
    for nt in (4, 8):
        before = batching.programs_held(CLASSES)
        A = _tiled(cholesky_mp.make_input(nt * 24, 9), 24)
        tp = dpotrf_mp_taskpool(A, 1, 2)
        ops.blocking.run_blocking(ctx, "dpotrf_mp", [tp])
        assert tp.reshape_repo.stats["conversions"] == converted_tiles(nt, 2)
        held.append(batching.programs_held(CLASSES) - before)
    programs = [fn for cache in batching._shared_cache.values()
                for fn in cache.values()]
    names = {fn.name for fn in programs}
    stackable = CLASSES - {"POTRF"}     # one POTRF is ready at a time
    buckets = {f"{c}_x{b}" for c in stackable for b in (2, 4, 8, 16)}
    assert names <= buckets
    # with bands (1, 2) no tile but the diagonal is hi
    assert {n.rsplit("_x", 1)[0] for n in names} \
        == {"TRSM_MID", "SYRK", "GEMM_MID", "GEMM_LO"}
    assert all(fn.fn._cache_size() == 1 for fn in programs)
    assert all(h <= len(buckets) + len(CLASSES) for h in held)
    assert len(call_sizes) >= 8 and max(call_sizes) >= 4


def test_no_body_reads_a_task_local():
    A = _tiled(np.eye(64, dtype=np.float32), 16)
    tp = dpotrf_mp_taskpool(A)
    assert {tc.name for tc in tp.task_classes} == CLASSES
    for tc in tp.task_classes:
        body, = tc.ast.bodies
        assert not {"k", "m", "n"} & set(compile(
            body.code, "<body>", "exec").co_names), tc.name
    assert {n: sorted(tc._typed_in) for n, tc in tp._classes.items()
            if tc._typed_in} == {"GEMM_LO": ["A", "B"]}


@pytest.mark.parametrize("n,nb", [(32768, 2048), (1024, 128), (512, 128),
                                  (128, 128)])
def test_the_kernel_files_count_the_dags_tasks_class_by_class(n, nb):
    cell = spec.Cell(spec.load_benchmark(), CELL)
    cell.resize(N=n, NB=nb)
    nt = n // nb
    A = TwoDimBlockCyclic(nt * 8, nt * 8, 8, 8, dtype=np.float32)
    tp = dpotrf_mp_taskpool(A, **cell.args)
    assert cell.kernel_counts() == {
        tc.name: sum(1 for _ in tc.iter_space()) for tc in tp.task_classes}
    assert cell.n_tasks() == nt * (nt + 1) * (nt + 2) // 6
    if nt == 16:
        assert cell.kernel_counts() == {
            "POTRF": 16, "TRSM": 15, "TRSM_MID": 105, "SYRK": 120,
            "GEMM": 105, "GEMM_MID": 235, "GEMM_LO": 220}
        assert cell.args == {"band_high": BH, "band_mid": BM}
        assert cell.config["levels"]["band_high"] == BH
        assert cell.config["levels"]["band_mid"] == BM


# ---- the tile kernels below `highest` ------------------------------------
def test_the_split_triangular_solve_is_the_solve():
    rng = np.random.default_rng(4)
    t = np.tril(rng.standard_normal((128, 128)).astype(np.float32)) / 16 \
        + 2 * np.eye(128, dtype=np.float32)
    c = rng.standard_normal((96, 128)).astype(np.float32)
    want = c.astype(np.float64) @ np.linalg.inv(t.astype(np.float64)).T
    whole = np.asarray(ops.trsm_panel(t, c))
    for leaf in (16, 32, 128):
        split = np.asarray(jax.jit(
            lambda t, c, leaf=leaf: ops.linalg.trsm_panel_split(
                t, c, jax.lax.Precision.HIGH, leaf))(t, c))
        assert np.abs(split - want).max() <= 4 * np.abs(whole - want).max() \
            + 1e-6 * np.abs(want).max()
    assert np.array_equal(np.asarray(ops.trsm_panel_mid(t, c)), whole)


def test_the_lo_kernel_takes_bf16_operands_and_writes_f32():
    rng = np.random.default_rng(5)
    c, a, b = (rng.standard_normal((64, 64)).astype(np.float32)
               for _ in range(3))
    abf, bbf = jnp.asarray(a, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16)
    got = ops.gemm_nt_lo(c, abf, bbf)
    assert got.dtype == jnp.float32
    want = c - np.asarray(abf, np.float32) @ np.asarray(bbf, np.float32).T
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-4)
    assert ops.gemm_nt_mid(c, a, b).dtype == jnp.float32


# ---- the comparison that decides `correct`, and its controls -------------
@pytest.mark.parametrize("level,lowered", [
    ("hi", {"bits": {"hi": 16}}),            # the hi band at `high`
    ("mid", {"bits": {"mid": 8}}),           # the mid band in one pass
    ("lo", {"bits": {"lo": 4}}),             # the lo band at 4 bits
])
def test_a_level_computed_one_step_lower_misses_its_limit(level, lowered):
    """The plain reference with ONE level's operands a step shorter: that
    level's number misses its limit (mid also drags the lo band's TRSM
    with it), the levels above it stay where they were."""
    n, nb, seed = 1024, 128, 2 ** 31 + 11
    M = cholesky_mp.make_input(n, seed)
    exp = cholesky_mp.expected(M, seed, nb, BH, BM)
    sound = cholesky_mp.level_numbers(
        cholesky_mp.plain_factor(M, nb, BH, BM), exp)
    low = cholesky_mp.level_numbers(
        cholesky_mp.plain_factor(M, nb, BH, BM, **lowered), exp)
    print(f"{level}: sound {sound} lowered {low} limits {LIMITS}")
    assert all(sound[lv] <= LIMITS[lv] for lv in cholesky_mp.LEVELS)
    assert low[level] > 2 * LIMITS[level]
    above = cholesky_mp.LEVELS[:cholesky_mp.LEVELS.index(level)]
    assert all(low[lv] <= LIMITS[lv] for lv in above)


def test_lo_products_accumulated_in_bf16_are_no_control():
    """A FINDING kept as a test: rounding each lo product to bf16 before
    it is subtracted raises the lo number by less than 3x, so no limit
    with room on both sides separates it from a sound run; the lo
    level's control is the operands at 4 bits."""
    n, nb, seed = 1024, 128, 2 ** 31 + 11
    M = cholesky_mp.make_input(n, seed)
    exp = cholesky_mp.expected(M, seed, nb, BH, BM)
    sound = cholesky_mp.level_numbers(
        cholesky_mp.plain_factor(M, nb, BH, BM), exp)
    acc = cholesky_mp.level_numbers(
        cholesky_mp.plain_factor(M, nb, BH, BM, lo_product_bits=8), exp)
    assert 1.0 < acc["lo"] / sound["lo"] < 3.0
    assert acc["hi"] == sound["hi"] and acc["mid"] == sound["mid"]


def _lo(x):
    return x.astype(jnp.bfloat16)


def _two_halves(x):
    """f32 with 16 significant bits: a bf16 half and the bf16 of what
    it left (what ``high`` makes of an operand)."""
    top = _lo(x).astype(jnp.float32)
    return top + _lo(x - top).astype(jnp.float32)


#: the hi band's products through the MXU, a level or two lower:
#: {name in ``ops``: kernel}
ONE_PASS_IN_HI = {"gemm_nt": lambda c, a, b: ops.gemm_nt_lo(c, _lo(a), _lo(b))}
HI_AT_HIGH = {
    "gemm_nt": lambda c, a, b: ops.linalg.gemm_nt(
        c, _two_halves(a), _two_halves(b)),
    "syrk_ln": lambda t, a: ops.linalg.syrk_ln(t, _two_halves(a))}


@pytest.mark.parametrize("fault,kernels,n,whole_matrix_sees_it", [
    ("a lo kernel in the hi band", ONE_PASS_IN_HI, 1024, True),
    ("the hi band at `high`", HI_AT_HIGH, 2048, False),
])
def test_a_lower_kernel_in_the_hi_band_is_not_correct_by_hi_alone(
        ctx, monkeypatch, fault, kernels, n, whole_matrix_sees_it):
    """The hi band's GEMM in one bf16 pass, or its GEMM and SYRK with
    16-bit operands (what the control `high` makes of them): the hi
    number alone misses its limit, so the run is not correct.  The
    whole-matrix residual of ``cholesky.residual`` is set by the lo
    band: it does not move when the hi band slips to `high`, which is
    why the check is band-wise (shown at NT = 16, where the lo band is
    most of the matrix); it does see a one-pass kernel there."""
    nb, seed = 128, 2 ** 31 + 13
    M = cholesky_mp.make_input(n, seed)
    exp = cholesky_mp.expected(M, seed, nb, BH, BM)
    whole = cholesky.expected(M, seed)
    A = _tiled(M, nb)
    ops.dpotrf_mp(ctx, A)
    sound = A.to_numpy()
    for name, kernel in kernels.items():
        monkeypatch.setattr(ops, name, jax.jit(kernel))
    B = _tiled(M, nb)
    ops.dpotrf_mp(ctx, B)
    broken = B.to_numpy()
    was = cholesky_mp.level_numbers(sound, exp)
    got = cholesky_mp.level_numbers(broken, exp)
    assert was["hi"] <= LIMITS["hi"] and was["mid"] <= LIMITS["mid"]
    assert not cholesky_mp.residual(broken, exp) <= 1.0, fault
    assert got["hi"] > 1.5 * LIMITS["hi"]
    # (the lo number depends on the shape: its limit is the cell's)
    assert got["mid"] <= LIMITS["mid"] and got["lo"] <= 1.2 * was["lo"]
    moved = cholesky.residual(broken, whole) / cholesky.residual(sound, whole)
    assert (moved > 10) if whole_matrix_sees_it else (moved < 1.5), moved


# ---- the harness through its CPU rehearsal, as a process -----------------
def test_the_cell_rehearses_as_a_process_with_counts_and_no_result_line():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PARSEC_MCA_")}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 17), "--seconds", "2",
         "--rehearse", "1024,128"],
        env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert all(ln.startswith("REHEARSAL ") for ln in lines)
    assert "120 tasks {'POTRF': 8, 'TRSM': 7, 'TRSM_MID': 21, 'SYRK': 28, " \
           "'GEMM': 21, 'GEMM_MID': 31, 'GEMM_LO': 4}" in lines[0]
    said = next(ln for ln in lines if "never a result" in ln)
    result = json.loads(said.split("): ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    n = result["attempted"]
    assert n >= 1 and list(result)[-1] == "compared"
    assert all(c["value"] <= c["limit"] == 1
               for c in result["compared"].values())
    window = next(ln for ln in lines if ln.startswith("REHEARSAL window:"))
    assert f"'tasks': {120 * n}," in window
    assert f"'conversions': {6 * n}," in window
    assert f"'reshape_hits': {2 * n}," in window
    # the reference's own line gives the three level numbers
    levels = [ln for ln in p.stderr.splitlines()
              if ln.startswith("check levels: ")]
    assert len(levels) == 2 and all(
        f"{lv} " in levels[0] for lv in cholesky_mp.LEVELS)
    assert lines[-1] == "REHEARSAL no result line: this was a CPU dry run"


def test_the_traced_readers_read_the_new_names():
    """The new per-layer readers on a hand-made observation: each finds
    its program by its trace name or its counter, and nothing where
    there is none."""
    bench = spec.load_benchmark()
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) >= 7 and all(m["moves"] == "factor_s" for m in mine)
    modules = {"jit_GEMM_LO_x8(1)": 0.02, "jit_GEMM_LO(2)": 0.01,
               "jit_GEMM_MID_x16(3)": 0.1, "jit_GEMM_x4(4)": 0.5,
               "jit_TRSM_MID_x2(5)": 0.04, "jit_TRSM(6)": 0.3,
               "jit_CONVERT(7)": 0.006}
    obs = {"trace": {"modules_s": modules}, "n_traced": 2, "chips": 1,
           "counters": {"conversions": 190}, "n_counted": 2, "walls": []}
    read = {m["name"]: spec.metric_reader(m["name"]).read for m in mine}
    assert read["gemm_lo_device_s"](obs) == pytest.approx(0.015)
    assert read["gemm_mid_device_s"](obs) == pytest.approx(0.05)
    assert read["trsm_mid_device_s"](obs) == pytest.approx(0.02)
    assert read["convert_device_s"](obs) == pytest.approx(0.003)
    assert read["conversions_per_factor"](obs) == 95
    assert spec.metric_reader("gemm_device_s").read(obs) \
        == pytest.approx(0.25)
    assert spec.metric_reader("trsm_device_s").read(obs) \
        == pytest.approx(0.15)
    empty = {"trace": {"modules_s": {"jit_GEMM_x4(4)": 0.5}}, "n_traced": 2,
             "chips": 1, "counters": {}, "n_counted": 2, "walls": []}
    for name in read:
        assert read[name](empty) is None, name
