"""Dispatch-count gates: each engine must reach the device in far fewer
calls than it has tasks, and still produce the right factor.

Upstream's tests/dsl/dtd/dtd_test_simple_gemm.c fails under a
``min_perf`` GFLOP/s; a rate on the CI host's CPU is not a speed, so
each gate here is the count the rate stood for (device calls against
tasks).  Speed is measured on the chip by ``perfbench/``.
"""
import numpy as np

import parsec_tpu
from parsec_tpu import dtd
from parsec_tpu.dsl.dtd import INOUT, INPUT, unpack_args


def test_dtd_simple_gemm_runs_on_the_device(ctx4):
    """Upstream's DTD tile GEMM (C[m][n] += A[m][k] B[k][n], chained
    over k by the INOUT tile): every task runs on an accelerator, in no
    more device calls than tasks, and the product is right."""
    import jax

    mt = nt = kt = 3
    nb = 64
    rng = np.random.RandomState(0)
    A = [[rng.rand(nb, nb).astype(np.float32) for _ in range(kt)]
         for _ in range(mt)]
    B = [[rng.rand(nb, nb).astype(np.float32) for _ in range(nt)]
         for _ in range(kt)]
    C = [[np.zeros((nb, nb), np.float32) for _ in range(nt)]
         for _ in range(mt)]

    tp = dtd.taskpool_new()
    ctx4.add_taskpool(tp)
    ta = [[tp.tile_of_array(A[m][k]) for k in range(kt)] for m in range(mt)]
    tb = [[tp.tile_of_array(B[k][n]) for n in range(nt)] for k in range(kt)]
    tc = [[tp.tile_of_array(C[m][n]) for n in range(nt)] for m in range(mt)]

    def gemm_body(es, task):
        c, a, b = unpack_args(task)
        c += a @ b

    gemm = tp.create_task_class("GEMM", 3, gemm_body)
    tp.add_chore(gemm, "tpu", jax.jit(lambda c, a, b: c + a @ b))
    devs = [d for d in ctx4.devices if d.device_type == "tpu"]
    assert devs, "no XLA device attached"
    keys = ("tasks", "dispatch_tasks", "batches", "batched_tasks")
    before = {k: sum(d.stats[k] for d in devs) for k in keys}
    for m in range(mt):
        for n in range(nt):
            for k in range(kt):
                tp.insert_task_with_task_class(
                    gemm, (tc[m][n], INOUT), (ta[m][k], INPUT),
                    (tb[k][n], INPUT))
    tp.data_flush_all()
    tp.wait()

    for m in range(mt):
        for n in range(nt):
            ref = sum(A[m][k].astype(np.float64) @ B[k][n]
                      for k in range(kt))
            got = np.asarray(tc[m][n].data.get_copy(0).payload)
            np.testing.assert_allclose(got, ref, atol=1e-3)

    st = {k: sum(d.stats[k] for d in devs) - before[k] for k in keys}
    n_tasks = mt * nt * kt
    assert st["tasks"] == st["dispatch_tasks"] == n_tasks, st
    calls = st["batches"] + st["dispatch_tasks"] - st["batched_tasks"]
    assert 0 < calls <= n_tasks, st
    assert st["batched_tasks"] >= 2 * st["batches"], st


def test_captured_dpotrf_is_one_program():
    """Graph capture: the whole 20-task DAG is ONE XLA program, built
    once and reused by every later call, and the factor is right."""
    import jax

    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.dsl import ptg
    from parsec_tpu.ops import dpotrf_taskpool, make_spd

    n, nb = 512, 128
    M = make_spd(n)
    A = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)
    cg = ptg.capture(dpotrf_taskpool(A))
    assert cg.nb_tasks == 20
    tiles = {"descA": {c: A.tile(*c) for c in A.tiles()}}
    for _ in range(3):
        out = cg.fn(tiles)
    jax.block_until_ready(out)
    assert cg.fn._cache_size() == 1, \
        "the captured DAG was traced again by a later call"
    Lf = np.zeros((n, n), np.float32)
    for (m, k), arr in out["descA"].items():
        Lf[m * nb:(m + 1) * nb, k * nb:(k + 1) * nb] = np.asarray(arr)
    L = np.tril(Lf)
    assert np.linalg.norm(L @ L.T - M) / np.linalg.norm(M) < 1e-5


def test_wave_dpotrf_dispatches_per_wave_not_per_task():
    """Wave execution at NB=512: a silent fall-back to one device call
    per task must FAIL.  The runner's own counters say how many kernel
    calls the DAG took: at most one per wave level and task class, so
    fewer than its tasks, with every kernel compiled once."""
    import jax

    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.dsl import ptg
    from parsec_tpu.ops import dpotrf_taskpool, make_spd

    n, nb = 2048, 512
    M = make_spd(n)
    A = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32).from_numpy(M)
    w = ptg.wave(dpotrf_taskpool(A))
    pools = w.execute(w.build_pools())
    jax.block_until_ready(pools)
    first = dict(w.stats)
    pools = w.execute(w.build_pools())   # warm: nothing new to compile
    jax.block_until_ready(pools)
    s = w.stats
    assert s["tasks"] == 20 and s["waves"] == 10, s
    assert s["kernel_calls"] <= s["waves"] * len(w.plans), s
    assert s["kernel_calls"] < s["tasks"], \
        f"{s['kernel_calls']} kernel calls for {s['tasks']} tasks: " \
        f"the batched dispatch path has regressed"
    assert s["kernel_calls"] == first["kernel_calls"]
    assert s["compiled_kernels"] == first["compiled_kernels"] > 0, \
        (first, s)

    w.scatter_pools(pools)
    L = np.tril(A.to_numpy()).astype(np.float64)
    ref = M.astype(np.float64)
    assert np.linalg.norm(L @ L.T - ref) / np.linalg.norm(ref) < 1e-5


def test_batched_dispatch_beats_per_task(call_sizes):
    """Device-module dispatch gate (ISSUE 5): a same-class 64-task
    burst must reach the device in far fewer calls than it has tasks.

    A call's host cost is fixed (~0.5 ms on a v5e host, whatever it
    holds), so what batching buys is the COUNT of calls; a count is the
    same on every host, where the seconds the calls took were a CPU
    timing under CI load.  One worker, the burst inserted before it
    runs: the ready set fills to device_batch_max, so every stacked
    call holds 16 tasks and none is carved into segments (single rank).
    What a call costs on the chip is perfbench's ``dispatch_s`` over
    ``tasks_per_call`` (PERF.md section 6, PR 27)."""
    import jax
    import jax.numpy as jnp

    import parsec_tpu
    from parsec_tpu import dtd
    from parsec_tpu.dsl.dtd import INOUT, INPUT
    from parsec_tpu.utils.params import params

    burst, nb = 64, 48
    kern = jax.jit(lambda c, a, b:
                   c - jnp.dot(a, b.T, preferred_element_type=jnp.float32))

    def run(batch_max):
        """(device calls, tasks they held, stacked calls among them)
        for the burst."""
        with params.cmdline_override("device_batch_max", str(batch_max)), \
             params.cmdline_override("device_tpu_max", "1"):
            ctx = parsec_tpu.init(nb_cores=1)
            try:
                devs = [d for d in ctx.devices
                        if d.device_type == "tpu"]
                assert devs, "no XLA device attached"
                tp = dtd.taskpool_new()
                ctx.add_taskpool(tp)

                def body(es, task):
                    c, a, b = dtd.unpack_args(task)
                    c -= a @ b.T

                tc = tp.create_task_class("GEMM", 3, body)
                tp.add_chore(tc, "tpu", kern)
                rng = np.random.RandomState(0)
                tiles = [[tp.tile_of_array(
                    rng.rand(nb, nb).astype(np.float32))
                    for _ in range(3)] for _ in range(burst)]
                for c, a, b in tiles:
                    tp.insert_task_with_task_class(
                        tc, (c, INOUT), (a, INPUT), (b, INPUT))
                tp.wait()
                st = {k: sum(d.stats[k] for d in devs)
                      for k in ("dispatch_tasks", "batches",
                                "batched_tasks")}
                lone = st["dispatch_tasks"] - st["batched_tasks"]
                return (st["batches"] + lone, st["dispatch_tasks"],
                        st["batches"])
            finally:
                ctx.fini()

    calls0, tasks0, b0 = run(1)
    assert not call_sizes
    calls1, tasks1, b1 = run(16)
    print(f"DISPATCH_GATE 64-burst nb={nb}: {calls1} device calls for "
          f"{tasks1} tasks batched (sizes {call_sizes}) vs {calls0} for "
          f"{tasks0} per task")
    assert tasks0 == tasks1 == burst
    assert b0 == 0 and calls0 == burst, (b0, calls0)
    assert b1 == len(call_sizes) > 0
    assert 16 in call_sizes, call_sizes
    # calls of 4 would make 16; whole groups make 4
    assert calls1 * 8 <= burst, \
        f"{calls1} device calls for {burst} same-class ready tasks: " \
        f"the stacked path has regressed (sizes {call_sizes})"
