"""One strip of LU's pivoted panel as a Mosaic kernel that keeps the
strip in VMEM (``ops.pallas_kernels.lu_strip_vmem``), held against the
XLA loop it stands in for on the TPU (``ops.linalg._lu_strip``).

On the CPU the kernel runs under ``interpret=True`` and has to give the
loop's strip, gather and pivots TO THE BIT: the two are one algorithm in
two lowerings, chosen by the platform a program is lowered for and by
the strip's shape, never by a parameter.  The last two tests compile for
a v5e that is described and not attached (one file holds them, the
topology is described inside a fixture: on-chip-measurement guide,
section 2).  Values and counts only: no time is asserted.
"""
import os
import re
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


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


def _same_bits(got, want):
    for g, w in zip(got, want):
        g, w = _bits(g), _bits(w)
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


def _strip(w, n, seed, quarters=False):
    x = np.random.default_rng(seed).standard_normal((w, n)).astype(np.float32)
    if quarters:        # many equal magnitudes; + 0.0: no negative zero
        x = (np.round(x * 4) / 4 + 0.0).astype(np.float32)
    return x


def _both(st, d0):
    """(the kernel's, the XLA loop's) strip, gather and pivots."""
    st, d0 = jnp.asarray(st), jnp.int32(d0)
    return (pk.lu_strip_vmem(st, d0, interpret=True),
            jax.jit(linalg._lu_strip)(st, d0))


@pytest.mark.parametrize("w", [32, 16])
@pytest.mark.parametrize("n", [256, 1024])
@pytest.mark.parametrize("where", ["top", "inside", "last"])
def test_strip_equals_the_xla_loop_to_the_bit(w, n, where):
    d0 = {"top": 0, "inside": 37, "last": n - w}[where]
    st = _strip(w, n, seed=n + w + d0)
    got, want = _both(st, d0)
    _same_bits(got, want)
    # a permutation of the rows, the identity above d0
    g = np.asarray(got[1])
    np.testing.assert_array_equal(np.sort(g), np.arange(n))
    np.testing.assert_array_equal(g[:d0], np.arange(d0))
    # the rows above d0 are never written
    np.testing.assert_array_equal(_bits(got[0])[:, :d0], _bits(st)[:, :d0])
    assert np.abs(np.tril(np.asarray(got[0])[:, d0:].T, -1)).max() <= 1.0


@pytest.mark.parametrize("d0", [0, 100])
def test_first_index_wins_a_tie(d0):
    w, n = 32, 512
    st = _strip(w, n, seed=7 + d0, quarters=True)
    st[0, d0 + 70], st[0, d0 + 20], st[0, d0 + 300] = 8.0, -8.0, 8.0
    got, want = _both(st, d0)
    _same_bits(got, want)
    assert int(got[2][0]) == d0 + 20
    # and wherever a later column's largest magnitude occurs twice
    _, ipiv = lu.plain_factor(st[:, d0:].T.astype(np.float64), w,
                              with_pivots=True)
    np.testing.assert_array_equal(np.asarray(got[2]), d0 + ipiv[:w])


def test_pivot_already_on_the_diagonal():
    """p == d in every step: the exchange reads and writes ONE row."""
    w, n, d0 = 16, 256, 37
    st = _strip(w, n, seed=3)
    st[np.arange(w), d0 + np.arange(w)] = 64.0 + np.arange(w)
    got, want = _both(st, d0)
    _same_bits(got, want)
    np.testing.assert_array_equal(np.asarray(got[2]), d0 + np.arange(w))
    np.testing.assert_array_equal(np.asarray(got[1]), np.arange(n))


def test_rows_above_d0_keep_their_bits():
    """Negative zeros and a NaN above the first active row come back as
    they went in, and the active rows do not see them."""
    w, n, d0 = 32, 1024, 300
    st = _strip(w, n, seed=11)
    st[:, :d0:7] = -0.0
    st[3, 5] = np.nan
    got, _ = _both(st, d0)
    np.testing.assert_array_equal(_bits(got[0])[:, :d0], _bits(st)[:, :d0])
    assert np.isfinite(np.asarray(got[0])[:, d0:]).all()
    clean = st.copy()
    clean[:, :d0] = 1.0
    other, _ = _both(clean, d0)
    np.testing.assert_array_equal(_bits(got[0])[:, d0:], _bits(other[0])[:, d0:])
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(other[2]))


def _panel_input(n, nb, r, seed=5):
    a = lu.make_input(n, seed)[:, :nb]
    q = np.zeros((linalg.PIV_ROWS, n), np.int32)
    q[0, 0], q[2] = r, np.arange(n)
    return a, q


def test_whole_panel_on_the_kernel_equals_the_loops(monkeypatch):
    n, nb, r = 512, 64, 128
    a, q = _panel_input(n, nb, r)
    want = linalg.getrf_1d_panel(a, q)          # the CPU: the XLA loop
    calls = []

    def kernel(st, d0):
        calls.append(st.shape)
        return pk.lu_strip_vmem(st, d0, interpret=True)

    monkeypatch.setattr(linalg, "_lu_strip_lowered", kernel)
    got = jax.jit(lambda a, q: linalg.getrf_1d_panel.__wrapped__(a, q))(a, q)
    assert calls == [(linalg.LU_STRIP, n)] * (nb // linalg.LU_STRIP)
    _same_bits(got, want)
    # the pivots are the float64 reference's
    _, ipiv = lu.plain_factor(a[r:].astype(np.float64), nb, with_pivots=True)
    np.testing.assert_array_equal(np.asarray(got[1])[3, r:r + nb],
                                  r + ipiv[:nb])
    np.testing.assert_array_equal(np.asarray(got[0])[:r], a[:r])


def test_shape_rule_takes_the_loop_where_the_kernel_does_not_fit(monkeypatch):
    assert pk.lu_strip_fits(32, 16384) and pk.lu_strip_fits(16, 256)
    assert pk.lu_strip_fits(32, 57344)
    assert not pk.lu_strip_fits(32, 250)        # rows do not fill lanes
    assert not pk.lu_strip_fits(32, 1 << 20)    # 128 MiB of strip
    monkeypatch.setattr(pk, "lu_strip_vmem", None)      # must not be called
    st = _strip(32, 250, seed=1)
    _same_bits(linalg._lu_strip_lowered(jnp.asarray(st), jnp.int32(9)),
               linalg._lu_strip(jnp.asarray(st), jnp.int32(9)))


def test_platform_rule_the_cpu_program_holds_no_mosaic_call():
    a, q = _panel_input(512, 64, 0)
    text = linalg.getrf_1d_panel.lower(a, q).as_text()
    assert "tpu_custom_call" not in text and "lu_strip_vmem" not in text
    assert "while" in text


def test_the_metric_reads_the_kernel_by_its_name_and_nothing_else():
    """``panel_strip_device_s``: listed for every LU cell and no other;
    the seconds of the ``XLA Ops`` that are the Mosaic call, a traced
    factorization; nothing where the trace has none (the parent)."""
    from perfbench import spec
    bench = spec.load_benchmark()
    entry = [m for m in bench["per_layer"]
             if m["name"] == "panel_strip_device_s"]
    assert len(entry) == 1 and entry[0]["moves"] == "factor_s"
    lu = [w["name"] for w in bench["workloads"]
          if spec.Cell(bench, w["name"]).op["entry"]
          .endswith(":dgetrf_1d")]
    assert len(lu) >= 2 and entry[0]["workloads"] == lu
    read = spec.metric_reader("panel_strip_device_s").read
    call = ('%lu_strip_vmem{} = (f32[32,128,128], s32[128,128], s32[32]) '
            'custom-call(s32[1] %r, f32[32,128,128] %c), '
            'custom_call_target="tpu_custom_call"')
    ops = {call.format(""): 0.25, call.format(".7"): 0.5,
           "%while.257 = (f32[32,16384]) while(%t)": 4.0,
           '%other = f32[8] custom-call(), custom_call_target="tpu_custom_call"':
           2.0}
    assert read({"trace": {"ops_s": ops}, "n_traced": 2}) == 0.375
    del ops[call.format("")], ops[call.format(".7")]
    assert read({"trace": {"ops_s": ops}, "n_traced": 2}) is None
    assert read({"trace": None, "n_traced": 0}) is None


# --- compiled for a described v5e: what interpret mode cannot show --------

@pytest.fixture(scope="module")
def one_chip():
    """The first device of a v5e:2x2 that is described, not attached,
    with the persistent compile cache off while these tests run (an
    entry compiled for a described chip cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_panel_program_for_the_v5e_is_sixteen_kernels_and_no_loop(one_chip):
    """PANEL at cell 6's shape: a strip kernel and a pass kernel a strip
    and no loop (no carry: neither the strip nor the panel); beside the
    pass kernels, which walk the panel in place, a handful of
    operations whose result is a whole panel (its copy in, the lane tiles' gathers joined at the end,
    one move out of VMEM and back that XLA schedules).  Before a pass
    moved only what the strip changed there were 76: a gather, two
    copies and a product a strip."""
    n, nb = 16384, 512
    a = jax.ShapeDtypeStruct((n, nb), jnp.float32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((linalg.PIV_ROWS, n), jnp.int32,
                             sharding=one_chip)
    text = linalg.getrf_1d_panel.lower(a, q).compile().as_text()
    strips = nb // linalg.LU_STRIP
    assert strips == 16
    assert len(re.findall(r"%lu_strip_vmem[.\d]* = ", text)) == strips
    assert len(re.findall(r"%lu_pass_vmem[.\d]* = ", text)) == strips
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * strips
    assert " while(" not in text
    entry = text[text.index("\nENTRY"):]
    whole = re.findall(r"^\s*(?:ROOT )?%(\S+) = f32\[16384,512\]\{[^}]*\} "
                       r"([\w\-]+)\(", entry, re.M)
    others = [name for name, op in whole
              if op not in ("parameter", "get-tuple-element")
              and not name.startswith("lu_pass_vmem")]
    assert len(others) <= 6, others


def test_strip_alone_compiles_for_the_v5e_at_a_full_chip_height(one_chip):
    n = 57344                   # the N at which f32 LU fills 16 GB
    assert pk.lu_strip_fits(linalg.LU_STRIP, n)
    st = jax.ShapeDtypeStruct((linalg.LU_STRIP, n), jnp.float32,
                              sharding=one_chip)
    d0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = jax.jit(pk.lu_strip_vmem).lower(st, d0).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("n,nb,c0", [
    (16384, 512, 0), (16384, 512, 96), (16384, 512, 480),
    (32768, 1024, 32), (32768, 1024, 992), (57344, 1024, 128)])
def test_pass_alone_compiles_for_the_v5e_in_place(one_chip, n, nb, c0):
    """The pass kernel at both LU cells' shapes and at the height at
    which f32 LU fills 16 GB: a strip at the left edge of its lane tile,
    at its right edge, the panel's last (nothing right of it): one
    Mosaic call, the panel aliased in and out."""
    import functools
    w = linalg.LU_STRIP
    assert pk.lu_pass_fits(w, n, nb)
    lo = pk.lu_pass_window(c0)

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        functools.partial(pk.lu_pass_vmem, c0=c0, c1=c0 + w),
        donate_argnums=0).lower(
            on((n, nb), jnp.float32), on((w, n), jnp.float32),
            on((2 * w,), jnp.int32), on((2 * w, nb - lo), jnp.float32),
            on((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "lu_pass_vmem" in text
    assert compiled.memory_analysis().alias_size_in_bytes == n * nb * 4


@pytest.mark.parametrize("n,nb", [(16384, 512), (32768, 1024),
                                  (40960, 1024)])
def test_update_program_for_the_v5e_is_one_kernel_and_no_gather(one_chip, n,
                                                                nb):
    """UPDATE at the three LU cells' shapes: the Mosaic call ``lu_update_vmem``
    and NOTHING else whose result is a whole column: no gather (the
    interchange moves 2 NB rows), no product over all N rows, and no
    copy before the call (the kernel writes a new column: the runtime
    does not donate a task's, and a call that wrote its operand would
    be handed a copy of it)."""
    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert pk.lu_update_fits(n, nb, nb)
    compiled = linalg.getrf_1d_update.lower(
        on((n, nb), jnp.float32), on((linalg.PIV_ROWS, n), jnp.int32),
        on((n, nb), jnp.float32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%lu_update_vmem[.\d]* = ", text)) == 1
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    entry = text[text.index("\nENTRY"):]
    column = rf"f32\[{n},{nb}\]" + r"\{[^}]*\} "
    whole = re.findall(r"^\s*(?:ROOT )?%(\S+) = " + column + r"([\w\-]+)\(",
                       entry, re.M)
    others = [(name, op) for name, op in whole
              if op not in ("parameter", "get-tuple-element")
              and not name.startswith("lu_update_vmem")]
    assert others == [], others
    assert not re.search(column + r"gather\(", text)
    # the column goes in as it is and a new one comes out: no second copy
    assert compiled.memory_analysis().temp_size_in_bytes < n * nb * 4 // 8


@pytest.mark.parametrize("n,nb", [(16384, 512), (32768, 1024),
                                  (57344, 1024)])
def test_update_kernel_alone_compiles_for_the_v5e(one_chip, n, nb):
    """The update kernel at both LU cells' shapes and at the height at
    which f32 LU fills 16 GB: one Mosaic call, inside the VMEM it asks
    for."""
    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert pk.lu_update_fits(n, nb, nb)
    text = jax.jit(pk.lu_update_vmem).lower(
        on((n, nb), jnp.float32), on((n, nb), jnp.float32),
        on((2 * nb,), jnp.int32), on((2 * nb, nb), jnp.float32),
        on((), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "lu_update_vmem" in text


@pytest.mark.parametrize("kernel,operands", [
    ("gemm_nt_mid", "fff"), ("gemm_nt_lo", "fhh"), ("trsm_panel_mid", "ff"),
])
def test_kernels_below_highest_compile_for_the_v5e_at_the_cells_tile(
        one_chip, kernel, operands):
    """The mixed-precision Cholesky's mid and lo kernels (ops/linalg.py)
    at NB = 2048 (kept in this file: one file's worker holds the TPU's
    compiler): each writes f32, lo takes bf16 operands, and a product
    of the mid kernels is split in three bf16 passes, of lo in none."""
    nb = 2048
    shapes = {"f": jax.ShapeDtypeStruct((nb, nb), jnp.float32,
                                        sharding=one_chip),
              "h": jax.ShapeDtypeStruct((nb, nb), jnp.bfloat16,
                                        sharding=one_chip)}
    with jax.default_matmul_precision("highest"):
        compiled = getattr(linalg, kernel).lower(
            *(shapes[c] for c in operands)).compile()
    out, = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.dtype == jnp.float32 and out.shape == (nb, nb)


def test_conversion_program_compiles_for_the_v5e(one_chip):
    from parsec_tpu.data.datatype import Datatype
    from parsec_tpu.data.reshape import conversion_program
    nb = 2048
    src = jax.ShapeDtypeStruct((nb, nb), jnp.float32, sharding=one_chip)
    compiled = conversion_program(
        Datatype(jnp.bfloat16, (nb, nb))).lower(src).compile()
    out, = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.dtype == jnp.bfloat16 and out.shape == (nb, nb)
    assert "jit_CONVERT" in compiled.as_text()


@pytest.mark.parametrize("nb,radius", [(4096, 1), (2048, 1), (8192, 1),
                                        (4096, 2)])
def test_stencil_program_for_the_v5e_is_one_kernel(one_chip, nb, radius):
    """The STENCIL body (``ops/stencil_1d.py``) at the cell's tile and
    the two by-hand sizes: ONE Mosaic call for the step (no shifted
    copy of the tile, no temporary), the ghosts as (radius, nb) arrays
    of whole lanes."""
    from parsec_tpu import ops
    weights = ops.stencil_weights(radius)

    def body(x, left, right):
        x = linalg.stencil_tile(x, left, right, weights)
        return (x,) + tuple(linalg.stencil_ghosts(x, radius))

    tile = jax.ShapeDtypeStruct((nb, nb), jnp.float32, sharding=one_chip)
    ghost = jax.ShapeDtypeStruct((radius, nb), jnp.float32,
                                 sharding=one_chip)
    compiled = jax.jit(body).lower(tile, ghost, ghost).compile()
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 1
    assert "stencil_tile_vmem" in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    x, gl, gr = jax.tree_util.tree_leaves(compiled.out_info)
    assert x.shape == (nb, nb) and gl.shape == gr.shape == (radius, nb)
