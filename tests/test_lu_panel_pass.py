"""LU's pivoted panel: a strip pass moves only what the strip changed
(``ops.linalg._lu_panel``, ``_lu_pass`` and the Mosaic kernel that
stands in for it on the TPU, ``ops.pallas_kernels.lu_pass_vmem``).

The panel of today is held TO THE BIT against the formulation it
replaced, kept here as :func:`parent_lu_panel` (every strip gathered the
whole panel and rewrote it twice): the same pivots, the same gather, the
same entries, on the CPU's XLA lowering, which is what every platform
but the TPU runs.  The kernel runs under ``interpret=True`` against
``_lu_pass``.  The tests that compile for a described v5e are in
``tests/test_lu_strip_vmem.py`` (one file holds the TPU's compiler).
Values and counts only: no time is asserted.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parsec_tpu.ops import linalg
from parsec_tpu.ops import pallas_kernels as pk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.reference import lu  # noqa: E402


def parent_lu_panel(x, r):
    """``_lu_panel`` as it was before a pass moved only what the strip
    changed (PR 36's): after every strip the whole panel is gathered by
    the strip's interchanges, the strip written into it, and the columns
    right of the strip rewritten with the product subtracted (the rows
    at or above the block row as ``x - 0 * u``)."""
    n, nb = x.shape
    lane = jnp.arange(n, dtype=jnp.int32)
    g = lane
    piv = jnp.zeros((nb,), jnp.int32)
    for c0 in range(0, nb, linalg.LU_STRIP):
        c1 = min(c0 + linalg.LU_STRIP, nb)
        d0 = r + c0
        st, gs, pv = linalg._lu_strip_lowered(x[:, c0:c1].T, d0)
        x = jnp.take(x, gs, axis=0, unique_indices=True, mode="clip")
        x = x.at[:, c0:c1].set(st.T)
        g = g[gs]
        piv = piv.at[c0:c1].set(pv)
        if c1 < nb:
            w = c1 - c0
            u = linalg.trsm_lower_unit(
                jax.lax.dynamic_slice(st, (0, d0), (w, w)).T,
                jax.lax.dynamic_slice(x, (d0, c1), (w, nb - c1)))
            x = jax.lax.dynamic_update_slice(x, u, (d0, c1))
            below = jnp.where(lane >= d0 + w, st, 0).T
            x = x.at[:, c1:].set(linalg.gemm_nn_sub(x[:, c1:], below, u))
    return x, g, piv


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


def _same_bits(got, want):
    for g, w in zip(got, want):
        g, w = _bits(g), _bits(w)
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


def _column(n, nb, seed, quarters=False):
    x = np.random.default_rng(seed).standard_normal((n, nb)).astype(np.float32)
    if quarters:        # many equal magnitudes; + 0.0: no negative zero
        x = (np.round(x * 4) / 4 + 0.0).astype(np.float32)
    return x


_both = (jax.jit(linalg._lu_panel), jax.jit(parent_lu_panel))


def _panels(x, r):
    """(today's, the parent's) column, gather and pivot rows."""
    x, r = jnp.asarray(x), jnp.int32(r)
    return _both[0](x, r), _both[1](x, r)


# (N, NB, r): one lane tile and several, a last strip narrower than the
# others, a last lane tile narrower than the others, one strip alone
SHAPES = [(256, 64, 0), (256, 64, 192), (512, 128, 128), (512, 256, 0),
          (512, 256, 256), (768, 384, 200), (640, 272, 100), (300, 80, 17),
          (128, 32, 0), (128, 32, 96)]


@pytest.mark.parametrize("n,nb,r", SHAPES)
def test_panel_equals_the_parents_to_the_bit(n, nb, r):
    x = _column(n, nb, seed=n + nb + r)
    got, want = _panels(x, r)
    _same_bits(got, want)
    g = np.asarray(got[1])
    np.testing.assert_array_equal(np.sort(g), np.arange(n))
    np.testing.assert_array_equal(g[:r], np.arange(r))
    # the pivots are the float64 reference's, every multiplier at most 1
    _, ipiv = lu.plain_factor(x[r:].astype(np.float64), nb, with_pivots=True)
    np.testing.assert_array_equal(np.asarray(got[2]), r + ipiv[:nb])
    assert np.abs(np.tril(np.asarray(got[0])[r:], -1)).max() <= 1.0


@pytest.mark.parametrize("n,nb,r", [(256, 64, 32), (512, 256, 128)])
def test_no_row_moves_where_every_pivot_is_on_the_diagonal(n, nb, r):
    x = _column(n, nb, seed=3)
    x[r + np.arange(nb), np.arange(nb)] = 64.0 + np.arange(nb)
    got, want = _panels(x, r)
    _same_bits(got, want)
    np.testing.assert_array_equal(np.asarray(got[2]), r + np.arange(nb))
    np.testing.assert_array_equal(np.asarray(got[1]), np.arange(n))


@pytest.mark.parametrize("n,nb,r", [(256, 64, 0), (512, 256, 64)])
def test_pivot_rows_inside_the_strips_own_block_row(n, nb, r):
    """The largest entries of a strip's columns lie in its own block
    row, out of order: the rows that moved name rows twice (a pivot row
    that is also a row of the block row ends as its row of U)."""
    x = _column(n, nb, seed=9) * 0.01
    w = linalg.LU_STRIP
    for c in range(nb):
        c0 = c // w * w
        x[r + c0 + (c - c0 + 5) % w, c] = 50.0 + c
    got, want = _panels(x, r)
    _same_bits(got, want)
    piv = np.asarray(got[2]) - r
    assert (piv // w == np.arange(nb) // w).all()       # inside the block row
    assert (piv != np.arange(nb)).any()                 # and rows did move


@pytest.mark.parametrize("n,nb,r", [(256, 64, 0), (512, 256, 100)])
def test_first_index_wins_a_tie(n, nb, r):
    x = _column(n, nb, seed=7 + r, quarters=True)
    x[r + 70, 0], x[r + 20, 0], x[r + 200, 0] = 8.0, -8.0, 8.0
    got, want = _panels(x, r)
    _same_bits(got, want)
    assert int(got[2][0]) == r + 20
    _, ipiv = lu.plain_factor(x[r:].astype(np.float64), nb, with_pivots=True)
    np.testing.assert_array_equal(np.asarray(got[2]), r + ipiv[:nb])


def _on_the_kernels(monkeypatch):
    """``_lu_panel`` jitted on both Mosaic kernels, interpreted; the
    passes it made are listed in ``.calls``."""
    calls = []

    def strip(st, d0):
        return pk.lu_strip_vmem(st, d0, interpret=True)

    def passes(x, st, rows, new, d0, *, c0, c1):
        calls.append((c0, c1))
        return pk.lu_pass_vmem(x, st, rows, new, d0, c0=c0, c1=c1,
                               interpret=True)

    monkeypatch.setattr(linalg, "_lu_strip_lowered", strip)
    monkeypatch.setattr(linalg, "_lu_pass_lowered", passes)
    panel = jax.jit(lambda x, r: linalg._lu_panel(x, r))
    panel.calls = calls
    return panel


@pytest.mark.parametrize("lowering,n,nb,r", [
    ("xla", 256, 64, 100), ("xla", 512, 256, 200),
    ("kernels", 512, 128, 100), ("kernels", 1024, 256, 700)])
def test_rows_above_r_keep_their_bits(lowering, n, nb, r, monkeypatch):
    """NaNs above the panel's first row come back as they went in and
    the rows under it do not see them; on the kernels negative zeros
    too (the XLA loop over a strip's columns rewrites an inactive row
    as ``x - 0 * y``, which loses a zero's sign: ``_lu_strip``)."""
    x = _column(n, nb, seed=11)
    x[5, 3] = x[r - 1, nb - 1] = np.nan
    if lowering == "kernels":
        x[:r:7] = -0.0
    want = _both[1](jnp.asarray(x), jnp.int32(r))
    panel = _both[0] if lowering == "xla" else _on_the_kernels(monkeypatch)
    got = panel(jnp.asarray(x), jnp.int32(r))
    _same_bits((got[0][r:],) + got[1:], (want[0][r:],) + want[1:])
    np.testing.assert_array_equal(_bits(got[0])[:r], _bits(x)[:r])
    assert np.isfinite(np.asarray(got[0])[r:]).all()
    clean = x.copy()
    clean[:r] = 1.0
    other = panel(jnp.asarray(clean), jnp.int32(r))
    np.testing.assert_array_equal(_bits(got[0])[r:], _bits(other[0])[r:])
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(other[2]))


def test_one_strip_alone_makes_no_pass(monkeypatch):
    """NB = LU_STRIP: the strip is the panel."""
    monkeypatch.setattr(linalg, "_lu_pass_lowered", None)   # must not be called
    n, nb = 128, linalg.LU_STRIP
    x = _column(n, nb, seed=1)
    _same_bits(linalg._lu_panel(jnp.asarray(x), jnp.int32(32)),
               parent_lu_panel(jnp.asarray(x), jnp.int32(32)))


# --- the pass as one Mosaic kernel, interpreted ---------------------------

def _pass_operands(n, nb, c0, d0, seed, inside=True):
    """A panel, a factored strip for its columns c0.. (transposed), the
    rows that moved (pivot rows twice, one inside the block row when
    ``inside``) and what they hold now, as ``_lu_panel`` hands them to a
    pass."""
    w = min(linalg.LU_STRIP, nb - c0)
    lo = pk.lu_pass_window(c0)
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, nb)).astype(np.float32)
    x[:d0:5] = -0.0
    x[0, nb - 1] = np.nan
    st = rng.standard_normal((w, n)).astype(np.float32)
    pv = rng.integers(d0 + w, n, size=w).astype(np.int32)
    pv[7] = pv[6]
    new = rng.standard_normal((2 * w, nb - lo)).astype(np.float32)
    new[7] = new[6]
    if inside:
        pv[3] = d0 + 5
        new[3, :c0 - lo] = new[w + 5, :c0 - lo]
    rows = np.concatenate([pv, d0 + np.arange(w, dtype=np.int32)])
    return tuple(jnp.asarray(a) for a in (x, st, rows, new)) + (jnp.int32(d0),)


@pytest.mark.parametrize("n,nb,c0,d0", [
    (1024, 256, 0, 0), (1024, 256, 32, 200), (1024, 256, 96, 511),
    (1024, 256, 128, 512), (1024, 256, 224, 700), (1536, 384, 160, 1100),
    (512, 128, 64, 470)])
def test_pass_kernel_equals_the_xla_pass_to_the_bit(n, nb, c0, d0):
    c1 = min(c0 + linalg.LU_STRIP, nb)
    assert pk.lu_pass_fits(c1 - c0, n, nb)
    x, st, rows, new, d = _pass_operands(n, nb, c0, d0, seed=n + c0 + d0)
    want, want_next = jax.jit(linalg._lu_pass, static_argnames=("c0", "c1"))(
        x, st, rows, new, d, c0=c0, c1=c1)
    got, got_next = pk.lu_pass_vmem(x, st, rows, new, d, c0=c0, c1=c1,
                                    interpret=True)
    x, st = np.asarray(x), np.asarray(st)
    lo = pk.lu_pass_window(c0)
    # the blocks of rows above the one that holds d0 are never brought in
    top = d0 // pk._LU_PASS_ROWS * pk._LU_PASS_ROWS
    np.testing.assert_array_equal(_bits(got)[:top], _bits(x)[:top])
    _same_bits([got[top:]], [want[top:]])
    if c1 == nb:
        assert got_next is None and want_next is None
    else:       # the next strip, as the strip kernel takes it: no row above d0 is read
        _same_bits([got_next[:, top:]], [want_next[:, top:]])
    got = np.asarray(got)
    # from there down: the strip in its columns; untouched: the lane tiles
    # left of the strip's, every row above the block row that did not move
    np.testing.assert_array_equal(_bits(got)[top:, c0:c1], _bits(st.T)[top:])
    np.testing.assert_array_equal(_bits(got)[:, :lo], _bits(x)[:, :lo])
    still = np.ones(n, bool)
    still[np.asarray(rows)] = False
    still[d0 + c1 - c0:] = False
    keep = np.ones(nb, bool)
    keep[c0:c1] = False
    np.testing.assert_array_equal(_bits(got)[still][:, keep],
                                  _bits(x)[still][:, keep])


def test_whole_panel_on_both_kernels_equals_the_parents(monkeypatch):
    n, nb, r = 1024, 256, 512
    x = _column(n, nb, seed=5)
    want = _both[1](jnp.asarray(x), jnp.int32(r))
    panel = _on_the_kernels(monkeypatch)
    got = panel(jnp.asarray(x), jnp.int32(r))
    w = linalg.LU_STRIP     # the last strip's pass: left of it only
    assert panel.calls == [(c, c + w) for c in range(0, nb, w)]
    _same_bits(got, want)


def test_shape_rule_takes_xla_where_the_pass_kernel_does_not_fit(monkeypatch):
    w = linalg.LU_STRIP
    assert pk.lu_pass_fits(w, 16384, 512) and pk.lu_pass_fits(w, 32768, 1024)
    assert pk.lu_pass_fits(w, 57344, 1024)
    assert not pk.lu_pass_fits(w, 16384 + 8, 512)   # no whole blocks of rows
    assert not pk.lu_pass_fits(w, 16384, 480)       # no whole lane tiles
    assert not pk.lu_pass_fits(w, 16384, 8192)      # 88 MiB of windows
    monkeypatch.setattr(pk, "lu_pass_vmem", None)           # must not be called
    x, st, rows, new, d = _pass_operands(300, 96, 32, 40, seed=2)
    _same_bits(linalg._lu_pass_lowered(x, st, rows, new, d, c0=32, c1=64),
               linalg._lu_pass(x, st, rows, new, d, c0=32, c1=64))


def test_platform_rule_the_cpu_program_holds_no_mosaic_call():
    a = jnp.zeros((1024, 256), jnp.float32)
    text = jax.jit(linalg._lu_panel).lower(a, jnp.int32(0)).as_text()
    assert "tpu_custom_call" not in text and "lu_pass_vmem" not in text


def test_the_pass_metric_is_the_panel_less_its_strip_kernel():
    """``panel_pass_device_s``: listed for every LU cell and no other;
    ``jit_PANEL``'s seconds less the strip kernel's, a traced
    factorization, so the pass kernel's own seconds stay inside it;
    nothing where the trace has no PANEL program or no strip kernel."""
    from perfbench import spec
    bench = spec.load_benchmark()
    entry = [m for m in bench["per_layer"]
             if m["name"] == "panel_pass_device_s"]
    assert len(entry) == 1 and entry[0]["moves"] == "factor_s"
    assert entry[0]["layer"] == "tile kernels"
    strip = [m for m in bench["per_layer"]
             if m["name"] == "panel_strip_device_s"]
    assert entry[0]["workloads"] == strip[0]["workloads"]
    read = spec.metric_reader("panel_pass_device_s").read
    call = ('%{}{} = (f32[32,128,128]) custom-call(s32[1] %r), '
            'custom_call_target="tpu_custom_call"')
    ops = {call.format("lu_strip_vmem", ""): 0.25,
           call.format("lu_strip_vmem", ".7"): 0.5,
           call.format("lu_pass_vmem", ".3"): 1.0,
           "%fusion.17 = f32[16384,512] fusion(%a)": 2.0}
    mods = {"jit_PANEL(123)": 6.0, "jit_UPDATE_x16(7)": 9.0}
    obs = {"trace": {"ops_s": ops, "modules_s": mods}, "n_traced": 2}
    assert read(obs) == 3.0 - 0.375
    assert read({"trace": {"ops_s": ops, "modules_s": {}}, "n_traced": 2}) \
        is None
    del ops[call.format("lu_strip_vmem", "")]
    del ops[call.format("lu_strip_vmem", ".7")]
    assert read(obs) is None
    assert read({"trace": None, "n_traced": 0}) is None
