"""Mesh-sharded device dispatch (ISSUE 6): a rank owning a chip MESH
(`device_mesh_shape`) places tiles block-cyclically across the chips and
compiles batched dispatch through shard_map — one jitted call per flush
group, spread over the mesh.  Runs on the conftest-forced 8-virtual-
device CPU host (XLA_FLAGS=--xla_force_host_platform_device_count=8),
the same substrate the dryrun multichip gate uses.
"""
import numpy as np
import pytest

import parsec_tpu
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.ops import dpotrf_taskpool, make_spd
from parsec_tpu.utils.params import params


def _mesh_ctx(shape="2x2", nb_cores=2):
    with params.cmdline_override("device_mesh_shape", shape):
        return parsec_tpu.init(nb_cores=nb_cores)


def test_mesh_device_attached_and_shaped():
    ctx = _mesh_ctx("2x2")
    try:
        dev = ctx.device_by_type("tpu")
        assert dev.mesh_shards == 4
        assert dev.grid == (2, 2)
        assert len({d.id for d in dev.chips}) == 4
        assert ctx.device_mesh is dev.mesh
        # the other devices list holds ONLY cpu + the mesh device
        assert [d.device_type for d in ctx.devices] == ["cpu", "tpu"]
    finally:
        ctx.fini()


def test_mesh_shape_parse():
    from parsec_tpu.devices.tpu import parse_mesh_shape
    assert parse_mesh_shape("2x2") == (2, 2)
    assert parse_mesh_shape("4") == (1, 4)
    assert parse_mesh_shape("") == (1, 1)
    assert parse_mesh_shape("1x1") == (1, 1)


def test_mesh_shape_beyond_the_chips_is_an_error():
    """Asking for more chips than exist is an error at context build:
    a quiet one-device-per-chip run would pass for a mesh run."""
    with pytest.raises(RuntimeError, match="needs 32 chips"):
        _mesh_ctx("8x4")


def test_mesh_block_cyclic_placement():
    """Collection tiles pin to their block-cyclic mesh position and the
    resident copy stays there (tiles live sharded across the mesh)."""
    A = TwoDimBlockCyclic(128, 128, 32, 32, dtype=np.float32)
    ctx = _mesh_ctx("2x2")
    try:
        dev = ctx.device_by_type("tpu")
        for (m, n) in A.tiles():
            pr, pc = A.mesh_position_of(m, n, dev.grid)
            assert (pr, pc) == (m % 2, n % 2)
            assert dev._chip_of(A.data_of(m, n)) is dev.chips[pr * 2 + pc]
    finally:
        ctx.fini()


def _run_dpotrf(n, nb, shape):
    """One classic-runtime dpotrf; returns (L, device stats)."""
    from contextlib import ExitStack
    with ExitStack() as stack:
        if shape:
            stack.enter_context(
                params.cmdline_override("device_mesh_shape", shape))
        else:
            stack.enter_context(
                params.cmdline_override("device_tpu_max", "1"))
        ctx = parsec_tpu.init(nb_cores=2)
        try:
            M = make_spd(n)
            A = TwoDimBlockCyclic(n, n, nb, nb,
                                  dtype=np.float32).from_numpy(M)
            ctx.add_taskpool(dpotrf_taskpool(A))
            ctx.wait()
            dev = ctx.device_by_type("tpu")
            return np.tril(A.to_numpy()), dict(dev.stats)
        finally:
            ctx.fini()


def test_mesh_dpotrf_bit_exact_vs_single_chip():
    """The sharded (unroll-mode) mesh path must be BIT-EXACT vs the
    single-chip batched path for the cholesky/trsm/syrk/gemm groups a
    dpotrf flushes — each per-example subgraph lowers identically on
    one chip whether the batch is stacked locally or spread over the
    mesh (ISSUE 6 acceptance)."""
    L_single, st_s = _run_dpotrf(256, 32, None)
    L_mesh, st_m = _run_dpotrf(256, 32, "2x2")
    assert st_s.get("mesh_dispatches", 0) == 0
    assert st_m["mesh_dispatches"] > 0, st_m
    assert st_m["mesh_tasks"] >= 4 * st_m["mesh_dispatches"]
    np.testing.assert_array_equal(L_mesh, L_single)


def test_mesh_dtd_burst_sharded_and_bit_exact():
    """Same-class DTD burst: the mesh leg must actually shard (one
    jitted call spread over the chips) and agree bit-exactly with the
    single-chip batched leg."""
    import jax
    import jax.numpy as jnp

    from parsec_tpu import dtd
    from parsec_tpu.dsl.dtd import INOUT, INPUT

    burst, nb = 16, 32
    kern = jax.jit(lambda c, a, b:
                   c - jnp.dot(a, b.T, preferred_element_type=jnp.float32))

    def run(shape):
        from contextlib import ExitStack
        with ExitStack() as stack:
            if shape:
                stack.enter_context(
                    params.cmdline_override("device_mesh_shape", shape))
            else:
                stack.enter_context(
                    params.cmdline_override("device_tpu_max", "1"))
            ctx = parsec_tpu.init(nb_cores=1)
            try:
                tp = dtd.taskpool_new()
                ctx.add_taskpool(tp)

                def body(es, task):
                    c, a, b = dtd.unpack_args(task)
                    c -= a @ b.T

                boot = tp.tile_of_array(np.zeros((nb, nb), np.float32))
                tp.insert_task(body, (boot, INOUT),
                               (boot, INPUT), (boot, INPUT))
                tp.add_chore(body, "tpu", kern)
                rng = np.random.RandomState(7)
                tiles = [[tp.tile_of_array(
                    rng.rand(nb, nb).astype(np.float32))
                    for _ in range(3)] for _ in range(burst)]
                for c, a, b in tiles:
                    tp.insert_task(body, (c, INOUT),
                                   (a, INPUT), (b, INPUT))
                tp.wait()
                dev = ctx.device_by_type("tpu")
                outs = [np.asarray(c.data.sync_to_host().payload)
                        for c, _a, _b in tiles]
                return outs, dict(dev.stats)
            finally:
                ctx.fini()

    outs_s, st_s = run(None)
    outs_m, st_m = run("2x2")
    assert st_m["mesh_dispatches"] > 0, st_m
    for a, b in zip(outs_s, outs_m):
        np.testing.assert_array_equal(a, b)


def test_mesh_sharded_trace_failure_downgrades_cleanly():
    """A class whose sharded compile fails must fall back to the
    single-chip stacked path WITHOUT losing tasks or correctness
    (spec.mesh_ok cleared, batchable kept)."""
    import jax
    import jax.numpy as jnp

    from parsec_tpu import dtd
    from parsec_tpu.dsl.dtd import INOUT, INPUT
    from parsec_tpu.devices import batching

    kern = jax.jit(lambda c, a: c + a)
    orig = batching.cached_sharded_callable

    def boom(*a, **kw):
        raise RuntimeError("injected sharded-compile failure")

    batching.cached_sharded_callable = boom
    try:
        with params.cmdline_override("device_mesh_shape", "2x2"):
            ctx = parsec_tpu.init(nb_cores=1)
            try:
                tp = dtd.taskpool_new()
                ctx.add_taskpool(tp)

                def body(es, task):
                    c, a = dtd.unpack_args(task)
                    c += a

                boot = tp.tile_of_array(np.zeros((8, 8), np.float32))
                tp.insert_task(body, (boot, INOUT), (boot, INPUT))
                tp.add_chore(body, "tpu", kern)
                rng = np.random.RandomState(3)
                tiles = [[tp.tile_of_array(
                    rng.rand(8, 8).astype(np.float32)) for _ in range(2)]
                    for _ in range(8)]
                for c, a in tiles:
                    tp.insert_task(body, (c, INOUT), (a, INPUT))
                tp.wait()
                dev = ctx.device_by_type("tpu")
                assert dev.stats["mesh_dispatches"] == 0
                assert dev.stats["batches"] > 0   # single-chip stacked
                rng = np.random.RandomState(3)
                for c, a in tiles:
                    cv = rng.rand(8, 8).astype(np.float32)
                    av = rng.rand(8, 8).astype(np.float32)
                    np.testing.assert_allclose(
                        np.asarray(c.data.sync_to_host().payload),
                        cv + av, rtol=1e-6)
            finally:
                ctx.fini()
    finally:
        batching.cached_sharded_callable = orig


def test_mesh_local_fast_path_multirank():
    """2 SPMD ranks, each owning a 2x2 chip mesh, classic runtime:
    intra-process dependencies ship device buffers BY REFERENCE
    (remote_dep mesh-local fast path) and the factorization stays
    correct."""
    from parsec_tpu.comm import LocalFabric, RemoteDepEngine
    from parsec_tpu.utils.spmd import spmd_threads

    n, nb, R = 128, 32, 2
    M = make_spd(n)

    def rank_fn(r, fab):
        eng = RemoteDepEngine(fab.engine(r))
        with params.cmdline_override("device_mesh_shape", "2x2"):
            ctx = parsec_tpu.Context(nb_cores=1, comm=eng)
        try:
            A = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32,
                                  P=2, Q=1, nodes=R, rank=r).from_numpy(M)
            A.name = "descA"
            ctx.add_taskpool(dpotrf_taskpool(A, rank=r, nb_ranks=R))
            ctx.wait()
            owned = {c: np.asarray(A.data_of(*c).sync_to_host().payload)
                     for c in A.tiles() if A.rank_of(*c) == r}
            return eng.stats["mesh_local_sends"], owned
        finally:
            ctx.fini()

    results, _ = spmd_threads(R, rank_fn, timeout=240)
    L = np.zeros((n, n))
    for (_ml, owned) in results:
        for (m, k), t in owned.items():
            L[m * nb:(m + 1) * nb, k * nb:(k + 1) * nb] = t
    L = np.tril(L)
    resid = np.abs(L @ L.T - M).max() / np.abs(M).max()
    assert resid < 1e-5, resid
    assert sum(ml for ml, _o in results) > 0, \
        "no activation took the mesh-local device-reference fast path"


def test_rank_mesh_sharding_carves_disjoint_chips():
    """The wave-pool sharding helper must give each rank the SAME chip
    slice the device layer carves (rank*chips offset), and shard tile
    dims over the ('tp','sp') axes."""
    import jax

    from parsec_tpu.dsl.ptg.wave_dist import rank_mesh_sharding

    sh0 = rank_mesh_sharding(0, shape="2x2")
    sh1 = rank_mesh_sharding(1, shape="2x2")
    assert sh0 is not None and sh1 is not None
    d0 = {d.id for d in sh0.mesh.devices.flat}
    d1 = {d.id for d in sh1.mesh.devices.flat}
    assert len(d0) == 4 and len(d1) == 4 and not (d0 & d1)
    assert rank_mesh_sharding(0, shape="1x1") is None
    # a pool staged with it spreads a tile over the sub-mesh
    x = np.zeros((3, 32, 32), np.float32)
    arr = jax.device_put(x, sh0)
    assert len(arr.addressable_shards) == 4
