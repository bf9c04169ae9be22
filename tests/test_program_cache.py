"""Each stacked program is built once per process: a PTG device body's
``cache_token`` is made from what the body reads (dsl/ptg/body_token.py),
so a fresh taskpool dispatches the programs an earlier one built.
Counts on the CPU backend; no time is asserted.
"""
import ast
import contextlib
import gc
import types
import weakref

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu import ops
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.devices import batching
from parsec_tpu.dsl import ptg
from parsec_tpu.dsl.ptg.body_token import body_reads, body_token
from parsec_tpu.utils.params import params

NB = 32
COUNTERS = ("first_calls", "program_reuse", "batches", "batch_downgrades")


@contextlib.contextmanager
def _one_device():
    """The one accelerator of a fresh context.  One worker and the
    DSL's static priorities (the dynamic ones are fed by measured
    dispatch times, and a loaded host reorders the classes), so a
    class's ready tasks reach the manager together: the buckets, and
    with them the counts below, are the same from run to run."""
    with params.cmdline_override("device_tpu_max", "1"), \
         params.cmdline_override("sched_dynamic_priority", "0"):
        ctx = parsec_tpu.init(nb_cores=1)
    d, = [d for d in ctx.devices if d.device_type == "tpu"]
    d.ctx = ctx
    try:
        yield d
    finally:
        del d.ctx
        ctx.fini()


@pytest.fixture
def dev(no_programs):
    with _one_device() as d:
        yield d


def _spd(n):
    return TwoDimBlockCyclic(n, n, NB, NB, dtype=np.float32).from_numpy(
        ops.make_spd(n))


def _run(dev, tp):
    """Run ``tp`` to its end; how far each counter moved."""
    before = {k: dev.stats[k] for k in COUNTERS}
    dev.ctx.add_taskpool(tp)
    dev.ctx.wait()
    return {k: dev.stats[k] - before[k] for k in COUNTERS}


def _specs(tp):
    return {tc.name: chore.batch_spec for tc in tp.task_classes
            for chore in tc.incarnations
            if getattr(chore, "batch_spec", None) is not None}


# ---------------------------------------------------------------- #
# (a) a second fresh taskpool builds nothing                       #
# ---------------------------------------------------------------- #
@pytest.mark.parametrize("op,make", [
    ("dpotrf", lambda: _spd(128)),
    ("dgeqrf", lambda: TwoDimBlockCyclic(
        128, 128, NB, NB, dtype=np.float32).from_numpy(
            np.random.RandomState(1).rand(128, 128).astype(np.float32))),
])
def test_a_fresh_taskpool_reuses_every_program(dev, op, make):
    taskpool = getattr(ops, op + "_taskpool")
    A, B = make(), make()
    first = _run(dev, taskpool(A))
    assert first["first_calls"] >= 1 and first["program_reuse"] == 0
    second = _run(dev, taskpool(B))
    assert second["first_calls"] == 0
    assert second["batches"] == first["batches"] >= 1
    assert second["program_reuse"] == second["batches"]
    assert second["batch_downgrades"] == 0
    np.testing.assert_array_equal(A.to_numpy(), B.to_numpy())


def test_a_fresh_taskpool_reuses_the_sharded_programs(no_programs):
    """``cached_sharded_callable`` picks its cache as the stacked path
    does: on a chip mesh the shard_map programs are shared too."""
    with params.cmdline_override("device_mesh_shape", "2x2"), \
         params.cmdline_override("sched_dynamic_priority", "0"):
        ctx = parsec_tpu.init(nb_cores=1)   # as in _one_device
    try:
        dev = ctx.device_by_type("tpu")
        dev.ctx = ctx
        keys = COUNTERS + ("mesh_dispatches",)
        moved, out = [], []
        for _ in range(2):
            A = _spd(256)
            before = {k: dev.stats[k] for k in keys}
            _run(dev, ops.dpotrf_taskpool(A))
            moved.append({k: dev.stats[k] - before[k] for k in keys})
            out.append(A.to_numpy())
        first, second = moved
        assert first["mesh_dispatches"] >= 1 and first["first_calls"] >= 1
        assert second["mesh_dispatches"] == first["mesh_dispatches"]
        assert second["first_calls"] == 0
        assert second["program_reuse"] == second["batches"]
        np.testing.assert_array_equal(out[0], out[1])
        del dev.ctx
    finally:
        ctx.fini()


# ---------------------------------------------------------------- #
# (b) the token names the kernel, not the module that holds it     #
# ---------------------------------------------------------------- #
def test_a_replaced_kernel_is_another_program(dev, monkeypatch):
    """The in-repo twin of perfbench's
    test_a_broken_timed_path_is_not_correct: a kernel replaced in the
    same process must show in the next taskpool's result."""
    sound = _spd(128)
    _run(dev, ops.dpotrf_taskpool(sound))
    with monkeypatch.context() as patched:
        patched.setattr(ops, "gemm_nt", lambda c, a, b: c)
        broken = _spd(128)
        moved = _run(dev, ops.dpotrf_taskpool(broken))
    assert moved["first_calls"] >= 1            # GEMM's, built anew
    assert 0 < moved["program_reuse"] < moved["batches"]   # TRSM, SYRK
    assert np.abs(np.tril(broken.to_numpy())
                  - np.tril(sound.to_numpy())).max() > 1e-3
    again = _spd(128)
    moved = _run(dev, ops.dpotrf_taskpool(again))
    assert moved["first_calls"] == 0            # the sound ones, kept
    np.testing.assert_array_equal(again.to_numpy(), sound.to_numpy())


# ---------------------------------------------------------------- #
# (c) a global goes in only if the body reads it                   #
# ---------------------------------------------------------------- #
def _token(tp, cls):
    spec = _specs(tp)[cls]
    assert batching.settle(spec)
    return spec.cache_token


def test_a_scalar_global_is_in_the_token_only_if_read():
    def rnd(n):
        return TwoDimBlockCyclic(n, n, NB, NB, dtype=np.float32)
    # GEQRT and TSQRT read NT (``k < NT - 1``) ...
    small, large = ops.dgeqrf_taskpool(rnd(128)), ops.dgeqrf_taskpool(rnd(256))
    for cls in ("GEQRT", "TSQRT"):
        assert _token(small, cls) is not None
        assert _token(small, cls) != _token(large, cls)
        assert _token(small, cls) == _token(ops.dgeqrf_taskpool(rnd(128)), cls)
    assert _token(small, "TSMQR") == _token(large, "TSMQR")
    # ... no dpotrf body does
    small, large = ops.dpotrf_taskpool(_spd(128)), ops.dpotrf_taskpool(_spd(256))
    for cls in ("TRSM", "SYRK", "GEMM"):
        assert _token(small, cls) == _token(large, cls) is not None


def test_another_size_of_matrix_shares_the_programs_of_a_tile_shape(dev):
    _run(dev, ops.dpotrf_taskpool(_spd(128)))
    gemm = _specs(ops.dpotrf_taskpool(_spd(128)))["GEMM"]
    batching.settle(gemm)
    built = set(batching._shared_cache[gemm.cache_token])
    A = _spd(256)
    moved = _run(dev, ops.dpotrf_taskpool(A))
    assert moved["program_reuse"] >= 1
    # any larger bucket is a new program in the same token's cache
    assert built <= set(batching._shared_cache[gemm.cache_token])
    L = np.tril(A.to_numpy()).astype(np.float64)
    np.testing.assert_allclose(L @ L.T, ops.make_spd(256), atol=1e-4)


# ---------------------------------------------------------------- #
# (d) what cannot be named by value gives no token                 #
# ---------------------------------------------------------------- #
ROWS_JDF = """
%(prologue)s
descA [ type="collection" ]
NT [ type="int" ]

Row(m)
m = 0 .. NT-1
: descA( m, 0 )
RW A <- descA( m, 0 )
     -> descA( m, 0 )
BODY [type=tpu]
{
    %(body)s
}
END
"""
HELPER = 'extern "C" %{\ndef helper(x):\n    return x + 1.0\n%}\n'


def _rows(body, prologue="", **env):
    tp = ptg.compile_jdf(ROWS_JDF % {"body": body, "prologue": prologue},
                         name="rows")
    A = TwoDimBlockCyclic(4 * NB, NB, NB, NB, dtype=np.float32).from_numpy(
        np.ones((4 * NB, NB), np.float32))
    tp = tp.new(descA=A, NT=4)
    tp.global_env.update(env)
    return tp, A


@pytest.mark.parametrize("body,prologue", [
    ("A = helper(A)", HELPER),                     # a prologue helper
    ("A = A + descA.mb / 32.0", ""),               # a collection
    ("A = eval('A + 1.0')", ""),                   # reads the source hides
    ("import jax.numpy as j\n    A = j.add(A, 1.0)", ""),
    ("A = getattr(jnp, 'add')(A, 1.0)", ""),       # a module, not through
                                                   # an attribute
])
def test_an_unnameable_body_caches_per_taskpool(dev, body, prologue):
    for _ in range(2):
        tp, A = _rows(body, prologue)
        moved = _run(dev, tp)
        spec = _specs(tp)["Row"]
        assert spec.cache_token is None and spec.late_token is None
        assert moved["batches"] >= 1 and moved["program_reuse"] == 0
        assert moved["first_calls"] == len(spec.cache) >= 1
        np.testing.assert_array_equal(A.to_numpy(), 2.0)
    assert batching._shared_cache == {}


@pytest.mark.parametrize("body,env,entries", [
    ("A = A + 1.0", {}, ()),
    ("A = jnp.add(A, 1.0)", {}, ("jnp.add",)),
    ("A = A + float(NT) - 3.0", {}, ("NT",)),
    ("A = A + ONE", {"ONE": 1.0}, ("ONE",)),
    ("A = kern.lax.add(A, kern.numpy.float32(1))",
     {"kern": __import__("jax")}, ("kern.lax.add", "kern.numpy.float32")),
    ("A = A + (1.0 if es_rank == 0 else 2.0)", {}, ()),
    ("f = lambda x: jnp.add(x, ONE)\n    A = f(A)", {"ONE": 1.0},
     ("ONE", "jnp.add")),
])
def test_a_nameable_body_shares_its_program(dev, body, env, entries):
    for i in range(2):
        tp, A = _rows(body, **env)
        moved = _run(dev, tp)
        token = _specs(tp)["Row"].cache_token
        assert token is not None
        assert tuple(e[0] for e in token[6]) == entries
        assert token[5] == (0 if "es_rank" in body else None)
        assert moved["batches"] >= 1
        if i == 0:
            assert moved["first_calls"] >= 1 and moved["program_reuse"] == 0
        else:
            assert moved["first_calls"] == 0
            assert moved["program_reuse"] == moved["batches"]
        np.testing.assert_array_equal(A.to_numpy(), 2.0)


def test_body_reads_and_scalars_by_repr():
    reads = body_reads(ast.parse(
        "x = ops.linalg.potrf(T)\nT = [ops.f(t) for t in (x, NT)]\nk += 1"))
    assert reads == {"ops": {("linalg", "potrf"), ("f",)}, "T": {()},
                     "x": {()}, "t": {()}, "NT": {()}, "k": {()}}
    assert body_reads(ast.parse("exec('T = 1')")) is None
    assert body_reads(ast.parse("from os import path")) is None

    def token(**env):
        src = "A = A * ALPHA"
        named = body_token("C[tpu]", src, compile(src, "<b>", "exec"),
                           [(0, "A")], [(0, "A")], [], 0, env)
        return named and named[0]
    assert token(ALPHA=1) != token(ALPHA=1.0) != token(ALPHA=True)
    assert token(ALPHA=0.0) != token(ALPHA=-0.0)
    assert token(ALPHA=float("nan")) == token(ALPHA=float("nan"))
    assert token(ALPHA=2.0, BETA=[1]) == token(ALPHA=2.0)   # BETA: not read
    assert token(ALPHA=np.float32(2)) is None       # an array scalar
    assert token(ALPHA=[2.0]) is None and token(ALPHA=len) is None
    assert token(A=[1], ALPHA=2.0) is not None      # A is the flow


# ---------------------------------------------------------------- #
# (e) the shared program holds no taskpool                         #
# ---------------------------------------------------------------- #
def test_a_shared_program_pins_no_taskpool_and_no_matrix(no_programs):
    """The device keeps the copies it staged for as long as it lives
    (``hbm_kept_gb_per_factor``), so the context goes too: what is left
    of the first run is the programs."""
    with _one_device() as dev:
        A = _spd(128)
        tp = ops.dpotrf_taskpool(A)
        first = _run(dev, tp)
        want = A.to_numpy()
        dead = [weakref.ref(tp), weakref.ref(A), weakref.ref(dev.ctx),
                weakref.ref(_specs(tp)["GEMM"]),
                weakref.ref(A.data_of(3, 1))]
    del tp, A, dev
    gc.collect()
    assert [r() for r in dead] == [None] * 5
    assert len(batching._shared_cache) == 3         # TRSM, SYRK, GEMM
    with _one_device() as dev:
        B = _spd(128)
        moved = _run(dev, ops.dpotrf_taskpool(B))
        # the same chip under a new device object: nothing to load
        assert moved["first_calls"] == 0
        assert moved["program_reuse"] == moved["batches"] == first["batches"]
        np.testing.assert_array_equal(B.to_numpy(), want)


# ---------------------------------------------------------------- #
# (f) a trace failure is remembered with the token                 #
# ---------------------------------------------------------------- #
def test_an_untraceable_body_is_traced_once_per_process(dev):
    import jax
    traced = []

    def scale(x):
        if isinstance(x, jax.core.Tracer):
            traced.append(x)
        return x * float(x[0, 0])       # concrete values only

    kern = types.ModuleType("kern")
    kern.scale = scale
    for i in range(3):
        tp, A = _rows("A = kern.scale(A) + 1.0", kern=kern)
        moved = _run(dev, tp)
        spec = _specs(tp)["Row"]
        assert not spec.batchable and spec.cache_token in batching._untraceable
        # every taskpool gives the stacked path up (a window that must
        # prove it stayed on it still sees the counter move) ...
        assert moved["batch_downgrades"] == 1 and moved["batches"] == 0
        assert len(traced) == 1     # ... only the first traced to find out
        np.testing.assert_array_equal(A.to_numpy(), 2.0)
    assert batching._shared_cache == {}


# ---------------------------------------------------------------- #
# the managers of several devices share one taskpool's specs       #
# ---------------------------------------------------------------- #
def test_concurrent_managers_settle_once_and_build_once(no_programs):
    import sys
    import threading
    asked, built = [], []
    token = ("stress", "GEMM[tpu]")

    def call(bargs, static):
        return (bargs[0] + bargs[1],)

    def late():
        asked.append(1)
        return token, call

    def spec_of(tok_late):
        return batching.DeviceBatchSpec("GEMM[tpu]", lambda t, a: None,
                                        call, late_token=tok_late)

    real = batching.build_stacked_callable

    def counting(spec, n, *a, **kw):
        built.append(n)
        return real(spec, n, *a, **kw)

    nthreads, rounds = 16, 20
    specs = [spec_of(late) for _ in range(rounds)]
    bad = [spec_of(lambda: (("stress", "bad"), call)) for _ in range(rounds)]
    batching._untraceable.add(("stress", "bad"))
    progs, gave_up = [], []
    start = threading.Barrier(nthreads)
    shapes = (((4, 4), "float32"),) * 2

    def manager():
        start.wait(timeout=30)
        for spec, b in zip(specs, bad):
            assert batching.settle(spec)
            progs.append(batching.cached_stacked_callable(
                spec, 2, 2, (), shapes))
            if not batching.settle(b):
                gave_up.append(b)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    batching.build_stacked_callable = counting
    try:
        threads = [threading.Thread(target=manager) for _ in range(nthreads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        batching.build_stacked_callable = real
        sys.setswitchinterval(old)
    assert len(asked) == rounds                  # each spec asked once
    assert built == [2]                          # one program, whoever won
    assert len(progs) == nthreads * rounds and len(set(map(id, progs))) == 1
    # each inherited downgrade is reported to exactly one manager
    assert len(gave_up) == rounds == len(set(map(id, gave_up)))
    assert not any(b.batchable for b in bad)
    assert all(s.cache_token == token and s.late_token is None
               for s in specs)
