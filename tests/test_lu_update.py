"""LU's UPDATE touches only the rows that are active
(``ops.linalg.getrf_1d_update``, ``_lu_update`` and the Mosaic kernel
that stands in for it on the TPU, ``ops.pallas_kernels.lu_update_vmem``).

The update of today is held TO THE BIT against the formulation it
replaced, kept here as :func:`parent_getrf_1d_update` (a gather of all N
rows by the panel's interchanges, then the product over all N rows with
those at or above the block row masked to zero), on the CPU's XLA
lowering, which is what every platform but the TPU runs.  The kernel
runs under ``interpret=True`` against ``_lu_update``: the rows above
the block row's last to the bit, the rows under it to rounding (another
tiling of the same sum over K).  The tests that compile for a described
v5e are in ``tests/test_lu_strip_vmem.py`` (one file holds the TPU's
compiler).  Values and counts only: no time is asserted.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parsec_tpu
from conftest import assert_ulp_close
from parsec_tpu import ops
from parsec_tpu.collections import BlockColumnCyclic
from parsec_tpu.ops import linalg
from parsec_tpu.ops import pallas_kernels as pk
from parsec_tpu.utils.params import params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.reference import lu  # noqa: E402


@jax.jit
def parent_getrf_1d_update(l, p, c):
    """``getrf_1d_update`` as it was before it touched only the active
    rows (PR 30's): the whole column gathered by the panel's
    interchanges ``p[1]``, the block row solved, and the product over
    all N rows, those of ``l`` at or above the block row masked to
    zero."""
    n = c.shape[0]
    nb = l.shape[1]
    r = p[0, 1]
    c = jnp.take(c, p[1], axis=0, unique_indices=True, mode="clip")
    u = linalg.trsm_lower_unit(
        jax.lax.dynamic_slice(l, (r, 0), (nb, nb)),
        jax.lax.dynamic_slice(c, (r, 0), (nb, c.shape[1])))
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    c = linalg.gemm_nn_sub(c, jnp.where(rows >= r + nb, l, 0), u)
    return jax.lax.dynamic_update_slice(c, u, (r, 0))


def _bits(x):
    return np.asarray(x).view(np.int32)


def pivot_tile(n, r, piv):
    """The pivot tile a panel at first row ``r`` leaves whose column
    step i exchanged row r + i with row ``piv[i]``."""
    nb = len(piv)
    g = np.arange(n, dtype=np.int32)
    for i, q in enumerate(piv):
        g[[r + i, q]] = g[[q, r + i]]
    p = np.zeros((linalg.PIV_ROWS, n), np.int32)
    p[0, 0], p[0, 1] = r + nb, r
    p[1], p[2] = g, g
    p[3, r:r + nb] = piv
    return p


def operands(n, nb, r, seed, piv=None, width=None):
    """(panel column, pivot tile, block column) of an update at first
    row ``r``: multipliers uniform in [-1, 1), the pivot of column step
    i drawn from the rows r + i .. n - 1 unless ``piv`` names them."""
    rng = np.random.default_rng(seed)
    l = (2 * rng.random((n, nb), dtype=np.float32) - 1).astype(np.float32)
    c = rng.standard_normal((n, width or nb)).astype(np.float32)
    if piv is None:
        piv = [int(rng.integers(r + i, n)) for i in range(nb)]
    return l, pivot_tile(n, r, np.asarray(piv, np.int32)), c


def _pivots(kind, n, nb, r, seed):
    """The pivot rows of a case: drawn from the active rows (None), all
    on the diagonal, all inside the panel's own block row, one row
    named twice, every one the last row."""
    rng = np.random.default_rng(seed)
    return {None: lambda: None,
            "diagonal": lambda: list(range(r, r + nb)),
            "inside": lambda: [int(rng.integers(r + i, r + nb))
                               for i in range(nb)],
            "twice": lambda: [n - 3, n - 3] + list(range(r + 2, r + nb)),
            "last": lambda: [n - 1] * nb}[kind]()


CASES = {
    # name: (n, nb, r, pivots: None = drawn from the active rows)
    "top": (256, 32, 0, None),
    "middle": (256, 32, 96, None),
    "last_step": (256, 32, 192, None),          # nb rows under the block row
    "r_is_no_multiple_of_nb": (256, 32, 40, None),
    "two_strips": (512, 64, 128, None),
    "wide_tile": (512, 128, 256, None),
    "identity": (256, 32, 64, "diagonal"),      # nothing moved
    "inside_the_block_row": (256, 32, 64, "inside"),
    "one_row_named_twice": (256, 32, 32, "twice"),
    "every_pivot_the_last_row": (256, 32, 0, "last"),
    "tall": (1024, 32, 512, None),
    "narrow_column": (256, 32, 64, None),
}


def _case(name):
    n, nb, r, kind = CASES[name]
    seed = sorted(CASES).index(name) + 2 ** 31
    width = 16 if name == "narrow_column" else None
    return operands(n, nb, r, seed, _pivots(kind, n, nb, r, seed),
                    width) + (n, nb, r)


@pytest.mark.parametrize("name", sorted(CASES))
def test_update_equals_the_parents_to_the_bit(name):
    l, p, c, n, nb, r = _case(name)
    got = linalg.getrf_1d_update(l, p, c)
    want = parent_getrf_1d_update(l, p, c)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # the rows above the panel's first keep their bits; the block row is U
    np.testing.assert_array_equal(_bits(got)[:r], _bits(c)[:r])
    if name == "identity":
        u = linalg.trsm_lower_unit(l[r:r + nb], c[r:r + nb])
        np.testing.assert_array_equal(_bits(got)[r:r + nb], _bits(u))


def test_update_of_a_real_panel_equals_the_parents_to_the_bit():
    """The pivot tile as ``getrf_1d_panel`` leaves it, ipiv of the
    earlier panels and all."""
    n, nb, r = 512, 64, 128
    a = lu.make_input(n, 2 ** 31 + 43)
    q = np.zeros((linalg.PIV_ROWS, n), np.int32)
    q[0, 0], q[2], q[3, :r] = r, np.arange(n), np.arange(r)[::-1]
    l, p = linalg.getrf_1d_panel(a[:, :nb], q)
    c = a[:, nb:2 * nb]
    got = linalg.getrf_1d_update(l, p, c)
    np.testing.assert_array_equal(
        _bits(got), _bits(parent_getrf_1d_update(l, p, c)))
    assert not np.array_equal(np.asarray(p[1]), np.arange(n))


@pytest.mark.parametrize("where", ["pivot_row", "block_row", "above"])
def test_nan_in_a_moved_row_goes_where_the_parents_goes(where):
    n, nb, r = 256, 32, 64
    l, p, c = operands(n, nb, r, seed=7)
    row = {"pivot_row": int(p[3, r + 5]), "block_row": r + 9,
           "above": 3}[where]
    c[row, 11] = np.nan
    got = np.asarray(linalg.getrf_1d_update(l, p, c))
    want = np.asarray(parent_getrf_1d_update(l, p, c))
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert np.isnan(got).any()


# --- the kernel, interpreted, against the XLA walk ------------------------

def _walk_operands(l, p, c):
    """What ``getrf_1d_update`` hands the walk."""
    nb = l.shape[1]
    r = int(p[0, 1])
    rows = np.concatenate([p[3, r:r + nb], r + np.arange(nb)]).astype(np.int32)
    new = np.asarray(c)[p[1][rows]]
    u = linalg.trsm_lower_unit(jnp.asarray(l[r:r + nb]), jnp.asarray(new[nb:]))
    new[nb:] = np.asarray(u)
    return (jnp.asarray(l), jnp.asarray(c), jnp.asarray(rows),
            jnp.asarray(new), jnp.int32(r))


KERNEL_CASES = {
    "top": (1024, 128, 0, None),
    "middle": (2048, 128, 512, None),
    "last_step": (1024, 128, 768, None),         # nb rows under
    "block_row_across_two_blocks": (2048, 128, 448, None),
    "r_is_no_multiple_of_eight": (1024, 128, 301, None),
    "identity": (1024, 128, 256, "diagonal"),
    "inside_the_block_row": (1024, 128, 128, "inside"),
    "one_row_named_twice": (1024, 128, 128, "twice"),
    "wide_column": (1024, 128, 384, None),
}


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_kernel_equals_the_xla_walk(name):
    n, nb, r, kind = KERNEL_CASES[name]
    seed = sorted(KERNEL_CASES).index(name) + 2 ** 31 + 100
    width = 256 if name == "wide_column" else nb
    l, p, c = operands(n, nb, r, seed, _pivots(kind, n, nb, r, seed), width)
    if name == "middle":            # the rows above r: never read
        c[:r:5] = -0.0
        c[7, 3] = np.nan
    assert pk.lu_update_fits(n, nb, width)
    args = _walk_operands(l, p, c)
    got = np.asarray(pk.lu_update_vmem(*args, interpret=True))
    want = np.asarray(jax.jit(linalg._lu_update)(*args))
    np.testing.assert_array_equal(_bits(got)[:r + nb], _bits(want)[:r + nb])
    np.testing.assert_array_equal(_bits(got)[:r], _bits(c)[:r])
    assert_ulp_close(got[r + nb:], want[r + nb:])


def test_shape_rule_takes_xla_where_the_kernel_does_not_fit(monkeypatch):
    assert pk.lu_update_fits(16384, 512, 512)
    assert pk.lu_update_fits(32768, 1024, 1024)
    assert pk.lu_update_fits(57344, 1024, 1024)     # f32 LU fills 16 GB
    assert not pk.lu_update_fits(256, 32, 32)       # no whole block of rows
    assert not pk.lu_update_fits(1024, 96, 128)     # K fills no whole lanes
    assert not pk.lu_update_fits(1024, 128, 100)
    assert not pk.lu_update_fits(32768, 2048, 2048)     # 126 MiB of VMEM
    monkeypatch.setattr(pk, "lu_update_vmem", None)     # must not be called
    l, p, c = operands(256, 32, 64, seed=3)
    np.testing.assert_array_equal(
        _bits(linalg.getrf_1d_update(l, p, c)),
        _bits(parent_getrf_1d_update(l, p, c)))


def test_platform_rule_the_cpu_program_holds_no_mosaic_call():
    l, p, c = operands(1024, 128, 256, seed=5)
    assert pk.lu_update_fits(1024, 128, 128)
    text = linalg.getrf_1d_update.lower(l, p, c).as_text()
    assert "tpu_custom_call" not in text and "lu_update_vmem" not in text
    assert "scatter" in text


# --- the factorization, end to end ----------------------------------------

@pytest.fixture(scope="module")
def ctx():
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=4)
    yield c
    c.fini()


@pytest.mark.parametrize("n,nb", [(256, 32), (512, 128)])
def test_factorization_is_the_references(ctx, n, nb):
    """``ops.dgetrf_1d`` on a small grid: the float64 reference's pivots,
    every multiplier at most 1, the residual under the cell's limit."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "dgetrf-f32-1chip.json")) as f:
        limit = json.load(f)["check"]["limit"]
    seed = 2 ** 31 + 43
    M = lu.make_input(n, seed)
    A = BlockColumnCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)
    ipiv = np.asarray(ops.dgetrf_1d(ctx, A))
    F = A.to_numpy()
    _, ref_ipiv = lu.plain_factor(M.astype(np.float64), nb, with_pivots=True)
    np.testing.assert_array_equal(ipiv, ref_ipiv)
    assert np.abs(np.tril(F, -1)).max() <= 1.0
    assert lu.residual(F, lu.expected(M, seed)) <= limit


def test_programs_held_are_what_they_were(monkeypatch):
    """One task at a time, at a shape the kernel's rule takes: a
    factorization holds one program a class, as before; the update
    brought none per panel index and none per lowering."""
    from parsec_tpu.devices import batching
    monkeypatch.setattr(batching, "_class_kernels", {})
    n, nb = 1024, 128
    assert pk.lu_update_fits(n, nb, nb)
    with params.cmdline_override("device_tpu_max", "1"), \
            params.cmdline_override("device_batch_max", "1"):
        c = parsec_tpu.init(nb_cores=4)
    try:
        classes = {"PANEL", "UPDATE", "LASWP"}
        before = batching.programs_held(classes)
        A = BlockColumnCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(
            lu.make_input(n, 9))
        ops.dgetrf_1d(c, A)
        assert batching.programs_held(classes) - before == 3
        assert np.abs(np.tril(A.to_numpy(), -1)).max() <= 1.0
    finally:
        c.fini()


def test_the_metric_reads_the_kernel_by_its_name_and_nothing_else():
    """``update_kernel_device_s``: listed for every LU cell and no
    other; the seconds of the ``XLA Ops`` that are the Mosaic call, a
    traced factorization; nothing where the trace has none (the
    parent)."""
    from perfbench import spec
    bench = spec.load_benchmark()
    entry = [m for m in bench["per_layer"]
             if m["name"] == "update_kernel_device_s"]
    assert len(entry) == 1 and entry[0]["moves"] == "factor_s"
    assert entry[0]["layer"] == "tile kernels"
    cells = [w["name"] for w in bench["workloads"]
             if spec.Cell(bench, w["name"]).op["entry"]
             .endswith(":dgetrf_1d")]
    assert len(cells) >= 2 and entry[0]["workloads"] == cells
    read = spec.metric_reader("update_kernel_device_s").read
    call = ('%lu_update_vmem{} = f32[16384,512] custom-call(s32[1] %r, '
            's32[1024] %rows, f32[16384,512] %c), '
            'custom_call_target="tpu_custom_call"')
    ops_s = {call.format(""): 0.25, call.format(".7"): 0.5,
             "%convolution_subtract_fusion.3 = f32[16384,512] fusion(%t)": 4.0,
             '%lu_pass_vmem.2 = f32[8] custom-call(), '
             'custom_call_target="tpu_custom_call"': 2.0}
    assert read({"trace": {"ops_s": ops_s}, "n_traced": 2}) == 0.375
    del ops_s[call.format("")], ops_s[call.format(".7")]
    assert read({"trace": {"ops_s": ops_s}, "n_traced": 2}) is None
    assert read({"trace": None, "n_traced": 0}) is None
