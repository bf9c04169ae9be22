"""``ops.stencil_1d`` (upstream's 1D stencil mini-app at tile scale)
against the plain reference; what its dataflow promises the device
module: tasks of every step in the same stacked programs, ghost regions
and not tiles handed on, and no host buffer or stage-in for a flow a
body only writes -- for the stencil and for ``ops.dgeqrf``'s Q / Q2."""
import os
import sys

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import ops
from parsec_tpu.collections import BlockColumnCyclic, TwoDimBlockCyclic
from parsec_tpu.data.data import Coherency, Data, DataCopy
from parsec_tpu.devices import batching
from parsec_tpu.dsl.ptg import runtime as ptg_runtime
from parsec_tpu.utils.params import params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.reference import stencil as ref  # noqa: E402

EPS = float(np.finfo(np.float32).eps)


def _input(n, seed, cols=None):
    rng = np.random.default_rng(seed)
    return (rng.random((n, cols or n)) - 0.5).astype(np.float32)


def _tiled(M, nb):
    return TwoDimBlockCyclic(M.shape[0], M.shape[1], nb, nb,
                             dtype=np.float32).from_numpy(M)


@pytest.fixture
def one():
    """A context with ONE accelerator: every tile is staged from the
    host, and the counts are the DAG's."""
    with params.cmdline_override("device_tpu_max", "1"):
        c = parsec_tpu.init(nb_cores=2)
    yield c
    c.fini()


def _accel(ctx):
    dev, = [d for d in ctx.devices if d.device_type == "tpu"]
    return dev


def _moved(dev, before):
    return {k: v - before[k] for k, v in dev.stats.items()
            if isinstance(v, (int, float))}


@pytest.mark.parametrize("radius", [1, 2])
@pytest.mark.parametrize("iterations", [1, 2, 7])
@pytest.mark.parametrize("nb", [8, 32])
@pytest.mark.parametrize("nt", [1, 2, 3, 5])
def test_against_the_plain_reference(one, nt, nb, iterations, radius):
    """Tolerance: a step is a convex combination of 2 R + 1 entries of
    magnitude at most 1/2, computed in f32 with 2 R + 1 products and
    2 R sums: at most (2 R + 2) units of 1/2 eps a step, summed over the
    steps (errors of earlier steps are averaged, never amplified)."""
    ctx = one
    M = _input(nt * nb, 7 * nt + nb + iterations + radius)
    A = _tiled(M, nb)
    dev = _accel(ctx)
    before = dict(dev.stats)
    ops.stencil_1d(ctx, A, iterations=iterations, radius=radius)
    want = ref.plain(M, iterations, radius, ops.stencil_weights(radius))
    tol = (2 * radius + 2) * iterations * EPS / 2
    assert np.abs(A.to_numpy() - want).max() <= tol
    d = _moved(dev, before)
    # every task on the accelerator; the tiles staged in once, nothing
    # else; each task's two ghost regions written on the device
    assert d["tasks"] == nt * nt * (iterations + 1)
    assert d["stage_in_bytes"] == M.nbytes
    assert d["scratch_stage_in_bytes"] == 0
    assert d["scratch_out_bytes"] == d["tasks"] * 2 * 4 * radius * nb
    assert d["batch_downgrades"] == 0


def test_rectangular_matrix_and_given_weights(ctx):
    """Rows are independent: the row count need not be the column
    count, nor the weights symmetric."""
    M = _input(48, 3, cols=64)
    A = _tiled(M, 16)
    w = (0.1, 0.6, 0.3)
    ops.stencil_1d(ctx, A, iterations=5, radius=1, weights=w)
    assert np.abs(A.to_numpy() - ref.plain(M, 5, 1, w)).max() <= 10 * EPS


def test_refused_arguments(ctx):
    A = _tiled(_input(16, 1), 8)
    with pytest.raises(ValueError):
        ops.stencil_1d(ctx, A, iterations=0)
    with pytest.raises(ValueError):
        ops.stencil_1d(ctx, A, iterations=1, radius=1, weights=(0.5, 0.5))
    with pytest.raises(ValueError):
        ops.stencil_1d(ctx, _tiled(_input(16, 1), 1), iterations=1, radius=2)


def _result(batch_max, M, nb, iterations, radius):
    with params.cmdline_override("device_batch_max", str(batch_max)), \
            params.cmdline_override("device_tpu_max", "1"):
        ctx = parsec_tpu.init(nb_cores=2)
        try:
            dev = _accel(ctx)
            A = _tiled(M, nb)
            ops.stencil_1d(ctx, A, iterations=iterations, radius=radius)
            return A.to_numpy(), dict(dev.stats)
        finally:
            ctx.fini()


@pytest.mark.parametrize("radius", [1, 2])
def test_alone_and_stacked_are_bit_equal(radius):
    """The weights are constants of the program in both forms, so a task
    dispatched alone and the same task in a stacked call give the same
    tile to the bit."""
    M = _input(5 * 16, 11)
    alone, st1 = _result(1, M, 16, 6, radius)
    stacked, stn = _result(16, M, 16, 6, radius)
    assert st1["batches"] == 0 and stn["batches"] > 0
    assert stn["batched_tasks"] > stn["tasks"] // 2
    assert np.array_equal(alone, stacked)


def test_programs_do_not_grow_with_the_steps(one):
    """No body reads a local, so no stacked program is keyed by the
    step.  What a shape can build: STENCIL's three forms (no L, no R,
    both) in four bucket sizes and alone, SNAP's one form likewise,
    and the ghosts' kernel under both names: 22 programs at most,
    after 3 steps as after 30, where a program a step would be 30 and
    more."""
    ctx = one
    M = _input(4 * 24, 5)       # a tile no other test has
    before = batching.programs_held({"STENCIL", "SNAP"})
    for iterations in (3, 30):
        ops.stencil_1d(ctx, _tiled(M, 24), iterations=iterations)
        built = batching.programs_held({"STENCIL", "SNAP"}) - before
        assert 0 < built <= 3 * 5 + 5 + 2, (iterations, built)


def test_edge_tiles_stack_among_themselves(one, call_sizes):
    """A 1 x 6 grid: the two edge tiles cannot stack with the four
    interior ones (their L or R is NULL), so a grid of several rows is
    what fills their buckets; on 6 x 6 most tasks ride stacked calls."""
    ctx = one
    M = _input(6 * 8, 9)
    dev = _accel(ctx)
    before = dict(dev.stats)
    ops.stencil_1d(ctx, _tiled(M, 8), iterations=4)
    d = _moved(dev, before)
    assert d["tasks"] == 36 * 5 and d["batch_downgrades"] == 0
    assert d["batched_tasks"] >= d["tasks"] // 2
    assert sum(call_sizes) == d["batched_tasks"]


def test_the_call_leaves_a_record_with_its_ghost_bytes(one):
    """One root span ``stencil_1d``; the record's ``by_device`` entry
    holds what the call's tasks wrote into runtime-made buffers beside
    ``stage`` and ``reshape``, and the report says so."""
    from parsec_tpu.obs import phases
    ctx = one
    phases.clear_completed()
    M = _input(3 * 8, 4)
    ops.stencil_1d(ctx, _tiled(M, 8), iterations=2)
    rec, = [r for r in phases.completed() if r["op"] == "stencil_1d"]
    entry, = rec["by_device"]
    assert entry["scratch"] == {"scratch_stage_in_bytes": 0,
                                "scratch_out_bytes": 9 * 3 * 2 * 4 * 8}
    assert entry["placement"]["tasks"] == 27
    assert "runtime-made buffers: 1728 bytes written by tasks, 0 staged" \
        in phases.format_report(rec)


def _parents_scratch_copy(self, f, env):
    """``PTGTaskpool.new_scratch_copy`` as the parent commit had it: a
    host buffer of zeros behind a Data of its own, for every flow."""
    shape = ptg_runtime.scratch_shape(f, env)
    dt = np.dtype(ptg_runtime.f_prop(f, "dtype", "float32"))
    data = Data(nb_elts=int(np.prod(shape)))
    copy = DataCopy(data, 0, payload=np.zeros(shape, dtype=dt))
    copy.coherency = Coherency.OWNED
    copy.version = 1
    data.attach_copy(copy)
    return copy


def _dgeqrf(ctx, M, nb):
    A = _tiled(M, nb)
    dev = _accel(ctx)
    before = dict(dev.stats)
    ops.dgeqrf(ctx, A)
    return A.to_numpy(), _moved(dev, before)


def test_a_write_only_flow_is_never_staged_in(one, monkeypatch):
    """dgeqrf's Q and Q2 are WRITE-only flows: no zeros go to the
    device (the bytes staged in are the matrix's, exactly), no buffer
    made on the host is read there, and R is the parent's to the bit."""
    ctx = one
    n, nb = 96, 16
    M = _input(n, 21)
    R, d = _dgeqrf(ctx, M, nb)
    assert d["stage_in_bytes"] == M.nbytes
    assert d["scratch_stage_in_bytes"] == 0
    nt = n // nb
    tsqrt = nt * (nt - 1) // 2
    # Q of every GEQRT whose row has an UNMQR to read it... and of the
    # last, which none reads; Q2 of every TSQRT
    assert d["scratch_out_bytes"] == 4 * (nt * nb * nb
                                          + tsqrt * (2 * nb) ** 2)
    monkeypatch.setattr(ptg_runtime.PTGTaskpool, "new_scratch_copy",
                        _parents_scratch_copy)
    R_parent, dp = _dgeqrf(ctx, M, nb)
    assert dp["stage_in_bytes"] > M.nbytes      # the zeros went too
    assert np.array_equal(R, R_parent)


def test_dgetrf_1d_is_the_parents_to_the_bit(ctx, monkeypatch):
    """PANEL's pivot tile is a WRITE-only flow too (NT - 1 of them a
    call; the last is bound to the collection's tile): the factor and
    the pivots are the parent's."""
    n, nb = 128, 32
    M = _input(n, 33)

    def lu():
        A = BlockColumnCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)
        ipiv = ops.dgetrf_1d(ctx, A)
        return A.to_numpy(), np.asarray(ipiv)

    LU, piv = lu()
    monkeypatch.setattr(ptg_runtime.PTGTaskpool, "new_scratch_copy",
                        _parents_scratch_copy)
    LU_parent, piv_parent = lu()
    assert np.array_equal(piv, piv_parent)
    assert np.array_equal(LU, LU_parent)


def test_a_host_body_gets_a_buffer_to_fill(ctx):
    """A WRITE-only flow first touched by a HOST body: the buffer is
    made then, and the body may fill it in place."""
    from parsec_tpu.collections import VectorTwoDimCyclic
    from parsec_tpu.dsl import ptg
    jdf = """
descU [ type="collection" ]
NT [ type="int" ]

FILL(t)

t = 0 .. NT-1

: descU( t, 0 )

WRITE G -> G TAKE( t )  [shape=4x1]

BODY
{
    G[:] = t + 1.0
}
END

TAKE(t)

t = 0 .. NT-1

: descU( t, 0 )

READ G <- G FILL( t )
RW   X <- descU( t, 0 )
       -> descU( t, 0 )

BODY
{
    X += G
}
END
"""
    U = VectorTwoDimCyclic(12, 4)
    for t in range(3):
        U.tile(t, 0)[:] = 0.0
    tp = ptg.compile_jdf(jdf, name="fill").new(descU=U, NT=3)
    ctx.add_taskpool(tp)
    ctx.wait()
    got = np.concatenate([U.tile(t, 0)[:, 0] for t in range(3)])
    assert np.array_equal(got, np.repeat([1.0, 2.0, 3.0], 4))


# --------------------------------------------------------------------- #
# the tile's step as a Mosaic kernel (interpret mode here; compiled    #
# for a described v5e in tests/test_lu_strip_vmem.py)                   #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("rows,nb,weights", [
    (256, 512, (0.25, 0.5, 0.25)),
    (128, 256, (0.05, 0.2, 0.4, 0.25, 0.1)),
    (384, 384, (0.1, 0.6, 0.3)),
])
def test_the_kernel_equals_the_xla_form_to_the_bit(rows, nb, weights):
    """One algorithm in two lowerings, chosen by the platform a program
    is lowered for and by the tile's shape, never by a parameter: the
    same products summed in the same order."""
    import functools

    import jax
    import jax.numpy as jnp
    from parsec_tpu.ops import linalg, pallas_kernels as pk
    r = len(weights) // 2
    assert pk.stencil_fits(rows, nb, r)
    rng = np.random.default_rng(rows + nb)
    x, left, right = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                      for shape in ((rows, nb), (r, rows), (r, rows)))
    got = pk.stencil_tile_vmem(x, left, right, weights=weights,
                               interpret=True)
    want = jax.jit(functools.partial(linalg._stencil_tile,
                                     weights=weights))(x, left, right)
    assert np.array_equal(np.asarray(got).view(np.int32),
                          np.asarray(want).view(np.int32))
    # and the form the body calls, with no neighbour on the left
    zero = jnp.zeros_like(left)
    assert np.array_equal(
        np.asarray(ops.stencil_tile(x, None, right, weights)),
        np.asarray(pk.stencil_tile_vmem(x, zero, right, weights=weights,
                                        interpret=True)))


def test_shape_and_platform_rules_of_the_kernel():
    """A tile the kernel does not take, and every program lowered for
    the CPU, run the XLA form: no Mosaic call in either."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.ops import pallas_kernels as pk
    assert not pk.stencil_fits(32, 32, 1)       # the tests' tiles
    assert not pk.stencil_fits(256, 128, 1)     # one slab is both edges
    assert pk.stencil_fits(4096, 4096, 1) and pk.stencil_fits(8192, 8192, 2)
    assert pk._stencil_rows(4096, 4096) == 128
    x = jnp.zeros((256, 512), jnp.float32)
    text = ops.stencil_tile.lower(x, None, None).compile().as_text()
    assert "tpu_custom_call" not in text


# --------------------------------------------------------------------- #
# the reference's probe identity                                        #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", [5, 2 ** 31 + 17])
def test_probe_identity_holds_for_plain_and_fails_without_a_ghost(seed):
    """U_I y = U_0 (B^I y) in exact arithmetic: ``plain`` itself reads
    rounding only; the same steps with every tile's ghosts dropped (each
    tile a problem of its own) is far off by both numbers."""
    n, nb = 128, 32
    U0 = ref.make_input(n, seed)
    exp = ref.expected(U0, seed)
    sound = ref.plain(U0).astype(np.float32)
    assert ref.probe_number(sound, exp) < 4 * EPS
    assert ref.rows_number(sound, exp) < 4 * EPS
    dropped = np.concatenate(
        [ref.plain(U0[:, c:c + nb]) for c in range(0, n, nb)],
        axis=1).astype(np.float32)
    assert ref.probe_number(dropped, exp) > 1e-2
    assert ref.rows_number(dropped, exp) > 1e-2
    assert ref.residual(dropped, exp) > 1e-2


def test_steps_on_vector_is_the_transposed_step():
    """(U B) y = U (B y) for weights that are not symmetric."""
    rng = np.random.default_rng(2)
    U = rng.standard_normal((6, 40))
    Y = rng.standard_normal((40, 2))
    w = (0.05, 0.2, 0.4, 0.25, 0.1)
    left = ref.plain(U, 3, 2, w) @ Y
    right = U @ ref.steps_on_vector(Y, 3, 2, w)
    assert np.allclose(left, right, rtol=1e-12, atol=1e-14)


# --------------------------------------------------------------------- #
# perfbench/checks/test_stencil.py (tier-1 runs ``tests/`` only)        #
# --------------------------------------------------------------------- #
from perfbench.checks.test_stencil import *  # noqa: E402,F401,F403
