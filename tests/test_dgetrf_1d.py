"""``ops.dgetrf_1d``: LU with partial pivoting through the runtime, held
against the plain float64 reference (``perfbench/reference/lu.py``), and
what it forced: tasks over whole block columns, an int32 pivot tile
beside f32 columns in one stacked call, programs that do not depend on
the panel index.  Counts and structure only: no time is asserted.
"""
import json
import os
import sys

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import ops
from parsec_tpu.collections import BlockColumnCyclic, TwoDimBlockCyclic
from parsec_tpu.devices import batching
from parsec_tpu.utils.params import params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.reference import lu  # noqa: E402

NB = 32
CLASSES = {"PANEL", "UPDATE", "LASWP"}
with open(os.path.join(ROOT, "perfbench", "configs",
                       "dgetrf-f32-1chip.json")) as _f:
    LIMIT = json.load(_f)["check"]["limit"]
EPS = float(np.finfo(np.float32).eps)


def _n_tasks(nt):
    return nt + nt * (nt - 1) // 2 + (nt - 1)


def _columns(M, nb=NB):
    return BlockColumnCyclic(*M.shape, nb, nb, dtype=np.float32).from_numpy(M)


def _accel(ctx):
    return [d for d in ctx.devices if d.device_type == "tpu"]


def _stat(ctx, key):
    return sum(d.stats[key] for d in _accel(ctx))


def _perm_of(ipiv, m=None):
    """LAPACK's ipiv (0-based) as a permutation of m rows:
    (P A)[i] = A[perm[i]]."""
    perm = np.arange(m or len(ipiv))
    for i, p in enumerate(ipiv):
        perm[i], perm[p] = perm[p], perm[i]
    return perm


def _factor(ctx, M, nb=NB):
    A = _columns(M, nb)
    ipiv = ops.dgetrf_1d(ctx, A)
    return A.to_numpy(), np.asarray(ipiv), ipiv


def _backward_error(F, perm, M):
    """max |L U - P A| over n eps max(|L| |U|): the backward error of
    Gaussian elimination in units of its bound's leading term."""
    n = M.shape[1]
    L = np.tril(F, -1).astype(np.float64)
    L[np.diag_indices(n)] = 1.0
    U = np.triu(F[:n]).astype(np.float64)
    err = np.abs(L @ U - M[perm].astype(np.float64)).max()
    return err / (n * EPS * (np.abs(L) @ np.abs(U)).max())


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


# ---- the factorization against the plain reference ----------------------
@pytest.mark.parametrize("n,nb", [
    (256, NB), (250, NB),    # 250: NB does not divide it
    (240, 80),               # a panel of two strips and a part of one
])
def test_factor_and_pivots_are_the_references(ctx, n, nb):
    """``P A = L U`` with the returned pivots; the pivot sequence is the
    float64 reference's (a pivot could differ only where two candidates
    agree to float32 rounding: none does on this seeded matrix); every
    multiplier is at most 1; every task ran on the accelerator.

    Tolerances.  Backward error: Gaussian elimination in float32 gives
    |LU - PA| <= c n eps |L||U| with c a small constant (Higham, Accuracy
    and Stability, thm 9.3); these matrices read 0.02-0.05 of n eps
    max(|L||U|), and 1 is the bound's own leading term.  Factor against
    the float64 reference: the same pivots, so the difference is the
    float32 rounding of each entry's history, bounded by the same
    n eps |L||U|; they read under 0.1 of it."""
    M = lu.make_input(n, 2 ** 31 + 5)
    before = _stat(ctx, "tasks")
    F, ipiv, _ = _factor(ctx, M, nb)
    nt = -(-n // nb)
    assert _stat(ctx, "tasks") - before == _n_tasks(nt)
    assert nb == NB or nb > 2 * ops.linalg.LU_STRIP
    ref, ref_ipiv = lu.plain_factor(M.astype(np.float64), nb,
                                    with_pivots=True)
    assert np.array_equal(ipiv, ref_ipiv)
    assert np.abs(np.tril(F, -1)).max() <= 1.0
    assert _backward_error(F, _perm_of(ipiv), M) <= 1.0
    scale = n * EPS * (np.abs(np.tril(ref, -1)) @ np.abs(np.triu(ref))
                       + np.abs(np.triu(ref))).max()
    assert np.abs(F - ref).max() <= scale
    assert lu.residual(F, lu.expected(M, 2 ** 31 + 5)) <= LIMIT


def test_tall_matrix(ctx):
    """More rows than columns: the last panel is taller than wide."""
    M = lu.make_input(192, 11)[:, :128].copy()
    F, ipiv, _ = _factor(ctx, M)
    assert len(ipiv) == 128
    assert np.abs(np.tril(F, -1)).max() <= 1.0
    assert _backward_error(F, _perm_of(ipiv, 192), M) <= 1.0


def test_zero_leading_diagonal_needs_pivoting(ctx):
    """A matrix whose leading diagonal entry is zero: the runtime's
    other LU (``dgetrf_nopiv``) divides by it and returns no finite
    factor; this one interchanges and factors it."""
    n = 128
    M = ops.make_diag_dominant(n, seed=3)
    M = np.ascontiguousarray(np.roll(M, 1, axis=0))   # diagonal goes
    M[0, 0] = 0.0                                      # under itself
    T = TwoDimBlockCyclic(n, n, NB, NB, dtype=np.float32).from_numpy(M)
    ops.dgetrf_nopiv(ctx, T)
    assert not np.isfinite(T.to_numpy()).all()
    F, ipiv, _ = _factor(ctx, M)
    assert np.isfinite(F).all()
    assert ipiv[0] != 0
    assert _backward_error(F, _perm_of(ipiv), M) <= 1.0


def test_refuses_square_tiles(ctx):
    T = TwoDimBlockCyclic(64, 64, NB, NB, dtype=np.float32)
    with pytest.raises(ValueError, match="block columns"):
        ops.dgetrf_1d(ctx, T)


def test_block_columns_tile_by_whole_columns():
    A = BlockColumnCyclic(100, 70, NB, NB, dtype=np.float32)
    assert (A.mt, A.nt, A.mb) == (1, 3, 100)
    assert list(A.tiles()) == [(0, 0), (0, 1), (0, 2)]
    assert A.tile_shape(0, 0) == (100, NB) and A.tile_shape(0, 2) == (100, 6)
    M = lu.make_input(100, 1)[:, :70].copy()
    assert np.array_equal(A.from_numpy(M).to_numpy(), M)


# ---- the path it takes ---------------------------------------------------
def test_stacked_and_one_at_a_time_agree_to_the_bit(ctx, one_at_a_time,
                                                    call_sizes):
    """The factor and the pivots are the same bits whether the updates
    go out in stacked calls or every task alone; the stacked run did
    stack, and no fast rung gave way."""
    M = lu.make_input(256, 77)
    down = [_stat(ctx, k) for k in ("batch_downgrades", "donate_retries")]
    F, ipiv, _ = _factor(ctx, M)
    assert call_sizes and max(call_sizes) >= 2
    assert [_stat(ctx, k) for k in ("batch_downgrades",
                                    "donate_retries")] == down
    del call_sizes[:]
    F1, ipiv1, _ = _factor(one_at_a_time, M)
    assert not call_sizes
    assert np.array_equal(F, F1) and np.array_equal(ipiv, ipiv1)


def test_pivot_tile_is_int32_beside_f32_in_one_stacked_call(ctx, monkeypatch):
    """The pivot flow is an int32 operand of the stacked UPDATE calls,
    beside the f32 columns; it is produced on the device and the pivots
    the call returns are still there (nothing staged out)."""
    from parsec_tpu.devices.tpu import JaxDevice
    seen = []
    stacked = JaxDevice._dispatch_stacked

    def recording(self, es, spec, static, shapes, donate, chunk):
        seen.append((spec.name, len(chunk),
                     [np.dtype(d).name for _, d in shapes], static[0]))
        return stacked(self, es, spec, static, shapes, donate, chunk)

    monkeypatch.setattr(JaxDevice, "_dispatch_stacked", recording)
    out0 = _stat(ctx, "stage_out_bytes")
    M = lu.make_input(256, 5)
    _, ipiv, on_device = _factor(ctx, M)
    updates = [s for s in seen if s[0].startswith("UPDATE")]
    assert updates
    for _, _, dtypes, local_values in updates:
        assert dtypes == ["float32", "int32", "float32"]
        assert local_values == ()    # no task local in the static key
    assert not isinstance(on_device, np.ndarray)    # a device array
    assert ipiv.dtype == np.int32 and ipiv.shape == (256,)
    # the only D2H of the test: to_numpy and np.asarray above, after
    # the call returned
    assert _stat(ctx, "stage_out_bytes") == out0


def test_lone_kernels_are_named_for_their_class(one_at_a_time):
    """A task dispatched alone runs ``jit_<CLASS>``: the rule
    ``batching.KernelsNamedFor`` applies to every class."""
    _factor(one_at_a_time, lu.make_input(64, 1))
    named = {name for (name, _fn) in batching._class_kernels}
    assert CLASSES <= named


def test_classes_are_on_the_phase_clock():
    """Under ``Context(profile=True)`` the call leaves one record named
    for the operation, every task of the three classes counted, and
    the Chrome export has an exec span per class."""
    from parsec_tpu.obs import phases
    phases.clear_completed()
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=2, profile=True)
    try:
        _factor(c, lu.make_input(128, 2))
        rec, = phases.completed()
        assert rec["op"] == "dgetrf_1d"
        assert rec["phases"]["complete"]["count"] == _n_tasks(4)
        names = {ev["name"] for ev in
                 c.profile.to_chrome_trace()["traceEvents"]}
        assert {"exec:PANEL", "exec:UPDATE", "exec:LASWP"} <= names
    finally:
        c.fini()
        phases.clear_completed()


@pytest.mark.parametrize("stacking", ["one_at_a_time", "stacked"])
def test_programs_held_do_not_depend_on_nt(ctx, one_at_a_time, no_programs,
                                           monkeypatch, stacking):
    """NT = 4 and NT = 8 at tile shapes of their own: one task at a
    time the classes hold the same programs (one kernel each), and with
    stacking every program is one of a class's few bucket sizes with
    ONE compiled signature -- none per panel index."""
    monkeypatch.setattr(batching, "_class_kernels", {})
    c = ctx if stacking == "stacked" else one_at_a_time
    held = []
    for n in (4 * 24, 8 * 24):      # NB = 24: shapes no other test has
        before = batching.programs_held(CLASSES)
        F, _, _ = _factor(c, lu.make_input(n, n), nb=24)
        assert np.abs(np.tril(F, -1)).max() <= 1.0
        held.append(batching.programs_held(CLASSES) - before)
    if stacking == "one_at_a_time":
        assert held == [3, 3]
        assert batching.programs_held({"PANEL"}) == 2   # one per N
    else:
        buckets = {f"{c}_x{b}" for c in ("UPDATE", "LASWP")
                   for b in (2, 4, 8, 16)}
        names = {fn.name for cache in batching._shared_cache.values()
                 for fn in cache.values()}
        assert names and names <= buckets
        # which buckets a run fills depends on how many tasks the
        # manager finds ready together; their number is bounded by the
        # bucket sizes, not by NT: a program per panel index would show
        # as 4 + 8 signatures of a class over the two runs
        assert all(h <= len(buckets) + len(CLASSES) for h in held)
        assert batching.programs_held({"PANEL"}) == 2
        assert batching.programs_held({"UPDATE"}) <= 2 * 5


# ---- the comparison that decides `correct`, and its controls -------------
def _no_pivot_factor(M):
    A = M.astype(np.float64)
    for j in range(A.shape[0] - 1):
        A[j + 1:, j] /= A[j, j]
        A[j + 1:, j + 1:] -= np.outer(A[j + 1:, j], A[j, j + 1:])
    return A


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11, 77])
def test_reference_misses_the_limit_below_its_precision(seed):
    """The plain reference in the program's place passes at the
    configuration's precision and misses the configuration's limit one
    precision below ('high': 16 bits) and two ('default': 8 bits).
    The number grows with N (it reads 1.5e-6 / 2.0e-4 at N = 512 and
    5.5e-6 / 6.6e-4 here; the chip 1.65e-5 / 4.8e-3 at the cell's
    16384), so the control needs a size at which 16 bits miss."""
    M = lu.make_input(2048, seed)
    exp = lu.expected(M, seed)
    sound = lu.residual(lu.plain_factor(M, 128, "highest"), exp)
    high = lu.residual(lu.plain_factor(M, 128, "high"), exp)
    low = lu.residual(lu.plain_factor(M, 128, "default"), exp)
    assert sound <= LIMIT < high < low


def test_reference_fails_a_factor_that_skipped_pivoting():
    """An exact LU with no pivoting reproduces A, but its multipliers
    exceed 1: not partial pivoting, so not ``correct``."""
    M = lu.make_input(128, 9)
    F = _no_pivot_factor(M)
    L = np.tril(F, -1) + np.eye(128)
    assert np.abs(L @ np.triu(F) - M).max() < 1e-6
    assert np.abs(np.tril(F, -1)).max() > 1.0
    assert lu.residual(F, lu.expected(M, 9)) == float("inf")


def test_reference_residual_ignores_the_permutation():
    """The check is handed the factor alone: any row order of A gives
    the same number for the same factor."""
    M = lu.make_input(128, 4)
    F = lu.plain_factor(M, 32)
    a = lu.residual(F, lu.expected(M, 4))
    b = lu.residual(F, lu.expected(M[::-1].copy(), 4))
    assert a == pytest.approx(b, rel=1e-6) and a <= LIMIT


def test_a_broken_interchange_is_not_correct(ctx, monkeypatch):
    """The left block columns keep the row order their own panel left:
    every later panel's pivots applied to one set of columns too few.
    Each column is still a sound LU of its own rows, and the factor as
    a whole is the LU of no row permutation."""
    M = lu.make_input(256, 2 ** 31 + 5)
    exp = lu.expected(M, 2 ** 31 + 5)
    F, _, _ = _factor(ctx, M)
    assert lu.residual(F, exp) <= LIMIT
    monkeypatch.setattr(ops, "getrf_1d_laswp", lambda a, p, f: a)
    F, _, _ = _factor(ctx, M)
    assert np.abs(np.tril(F, -1)).max() <= 1.0
    assert not lu.residual(F, exp) <= LIMIT
