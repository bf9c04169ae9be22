"""Overlap-aware execution (ISSUE 7): whole-group flush bit-exactness +
fallback, remote-GET prefetch for early activations, and the live
overlap tracker's interval algebra."""
import os
import sys
import time

import numpy as np
import pytest

import parsec_tpu
from conftest import spmd
from parsec_tpu import dtd
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.comm import RemoteDepEngine
from parsec_tpu.dsl import ptg
from parsec_tpu.dsl.dtd import INOUT, INPUT
from parsec_tpu.ops import dpotrf_taskpool, make_spd
from parsec_tpu.utils.params import params


def _tpu_devs(ctx):
    return [d for d in ctx.devices if d.device_type == "tpu"]


# --------------------------------------------------------------------- #
# one stacked call per flush group, on one rank and across ranks:       #
# bit-exact against per-task dispatch + counters + fallback             #
# --------------------------------------------------------------------- #
STACK_STATS = ("batches", "batched_tasks")


def _on_ranks(nb_ranks, body):
    """``body(ctx, rank)`` in a context of ``nb_ranks`` in-process ranks
    (one plain context, or one per thread over a LocalFabric); the
    per-rank results."""
    if nb_ranks == 1:
        ctx = parsec_tpu.Context(nb_cores=2)
        try:
            return [body(ctx, 0)]
        finally:
            ctx.fini()

    def rank_fn(rank, fabric):
        ctx = parsec_tpu.Context(nb_cores=2,
                                 comm=RemoteDepEngine(fabric.engine(rank)))
        try:
            return body(ctx, rank)
        finally:
            ctx.fini()

    return spmd(nb_ranks, rank_fn)[0]


def _stack_stats(ctx):
    devs = _tpu_devs(ctx)
    return {k: sum(d.stats[k] for d in devs) for k in STACK_STATS}


def _sum_stats(per_rank):
    return {k: sum(st[k] for st in per_rank) for k in STACK_STATS}


def _run_dpotrf(nb_ranks: int = 1, batch_max: int = 16):
    """One classic-runtime dpotrf (POTRF/TRSM/SYRK/GEMM classes, N=256,
    NB=32) over ``nb_ranks`` in-process ranks; returns (L, stacked-call
    stats summed over the ranks)."""
    n, nb = 256, 32
    M = make_spd(n)

    def body(ctx, rank):
        A = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32, P=nb_ranks,
                              Q=1, nodes=nb_ranks, rank=rank)
        A.name = "descA"
        A.from_numpy(M.copy())
        ctx.add_taskpool(dpotrf_taskpool(A, rank=rank, nb_ranks=nb_ranks))
        ctx.wait()
        owned = {c: np.asarray(A.data_of(*c).sync_to_host().payload)
                 for c in A.tiles() if A.rank_of(*c) == rank}
        return owned, _stack_stats(ctx)

    with params.cmdline_override("device_tpu_max", "1"), \
         params.cmdline_override("device_batch_max", str(batch_max)):
        results = _on_ranks(nb_ranks, body)
    L = np.zeros((n, n), np.float32)
    for owned, _st in results:
        for (tm, tk), t in owned.items():
            L[tm * nb:tm * nb + t.shape[0],
              tk * nb:tk * nb + t.shape[1]] = t
    return L, _sum_stats([st for _owned, st in results])


@pytest.mark.parametrize("nb_ranks", [1, 2])
def test_dpotrf_flushes_each_group_whole(call_sizes, nb_ranks):
    """A flush group is ONE stacked call of up to device_batch_max
    tasks, on one rank and across ranks (``batches`` counts exactly the
    calls seen), bit-identical to per-task dispatch for the
    cholesky/trsm/syrk/gemm classes."""
    L, st = _run_dpotrf(nb_ranks)
    sizes = list(call_sizes)
    assert st["batches"] == len(sizes)
    assert st["batched_tasks"] == sum(sizes)
    assert max(sizes) >= 8, sizes   # the deleted segments capped at 4
    assert set(sizes) <= {2, 4, 8, 16}, sizes
    L_each, st_each = _run_dpotrf(nb_ranks, batch_max=1)
    assert st_each["batches"] == 0
    assert np.array_equal(L, L_each), \
        "a stacked call is not bit-identical to per-task dispatch"
    Lt = np.tril(L).astype(np.float64)
    M = make_spd(256)
    assert np.abs(Lt @ Lt.T - M).max() / np.abs(M).max() < 1e-5


def _run_dtd_burst(kern, burst=32, nb=48, nb_ranks=1, batch_max=16):
    """A burst of independent same-class tasks on rank 0's device (a
    keyless tile's home is rank 0; every rank inserts the same stream,
    SPMD); returns (rank 0's outputs, rank 0's stacked-call stats)."""
    def body(ctx, rank):
        tp = dtd.taskpool_new()
        ctx.add_taskpool(tp)

        def host(es, task):   # host fallback
            c, a, b = dtd.unpack_args(task)
            c -= a @ b.T

        tc = tp.create_task_class("GEMM", 3, host)
        tp.add_chore(tc, "tpu", kern)
        rng = np.random.RandomState(7)
        tiles = [[tp.tile_of_array(rng.rand(nb, nb).astype(np.float32))
                  for _ in range(3)] for _ in range(burst)]
        for c, a, b in tiles:
            tp.insert_task_with_task_class(tc, (c, INOUT), (a, INPUT),
                                           (b, INPUT))
        tp.wait()
        out = [np.asarray(c.data.sync_to_host().payload)
               for c, _a, _b in tiles]
        return out, _stack_stats(ctx)

    with params.cmdline_override("device_tpu_max", "1"), \
         params.cmdline_override("device_batch_max", str(batch_max)):
        return _on_ranks(nb_ranks, body)[0]


def _gemm_kern():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda c, a, b:
                   c - jnp.dot(a, b.T, preferred_element_type=jnp.float32))


@pytest.mark.parametrize("nb_ranks", [1, 2])
def test_dtd_burst_flushes_each_group_whole(call_sizes, nb_ranks):
    """A 32-task DTD burst on rank 0's device: stacked calls of 16,
    each ONE call on one rank and across ranks, bit-identical to
    per-task dispatch."""
    kern = _gemm_kern()
    out, st = _run_dtd_burst(kern, nb_ranks=nb_ranks)
    sizes = list(call_sizes)
    assert 16 in sizes, sizes
    assert st["batches"] == len(sizes)
    assert st["batched_tasks"] == sum(sizes)
    out_each, st_each = _run_dtd_burst(kern, nb_ranks=nb_ranks, batch_max=1)
    assert st_each["batches"] == 0
    assert all(np.array_equal(a, b) for a, b in zip(out, out_each))


@pytest.mark.parametrize("nb_ranks", [1, 2])
def test_untraceable_falls_back_per_task(nb_ranks):
    """A trace failure inside the first call of a group must downgrade
    the class and finish the whole group per-task — the same
    transparent fallback on one rank and across ranks, results
    unchanged."""
    def kern(c, a, b):   # np.asarray on a tracer raises under jit
        return c - np.asarray(a) @ np.asarray(b).T

    out, st = _run_dtd_burst(kern, burst=16, nb_ranks=nb_ranks)
    assert st["batches"] == 0, "untraceable body must not batch"
    rng = np.random.RandomState(7)
    tiles = [[rng.rand(48, 48).astype(np.float32) for _ in range(3)]
             for _ in range(16)]
    for got, (c, a, b) in zip(out, tiles):
        np.testing.assert_allclose(got, c - a @ b.T, atol=1e-4)


# --------------------------------------------------------------------- #
# remote-GET prefetch: an activation racing ahead of registration       #
# --------------------------------------------------------------------- #
PREFETCH_JDF = """
descX [ type="collection" ]

PROD(k)

k = 0 .. 0

: descX( 0, 0 )

RW X <- descX( 0, 0 )
     -> X CONS( 0 )
     -> descX( 0, 0 )

BODY
{
    X[:, :] = X + 1.0
}
END

CONS(k)

k = 0 .. 0

: descX( 1, 0 )

READ X <- X PROD( 0 )
RW   Y <- descX( 1, 0 )
       -> descX( 1, 0 )

BODY
{
    Y[:, :] = X * 2.0
}
END
"""


def test_remote_get_prefetch_early_activation():
    """Rank 1 delays its taskpool registration while rank 0 completes
    PROD and ships the activation: the 32 KB payload (> short_limit)
    rides a rendezvous handle, the activation is buffered early, and
    the GET must be PREFETCHED while buffered — the replayed delivery
    then hits the prefetched payload, never issuing a second GET."""
    nb_ranks, mb = 2, 64   # 64x64 f64 = 32 KB > 4096 (rendezvous)
    A0 = np.random.RandomState(3).rand(2 * mb, mb)

    def rank_fn(rank, fabric):
        eng = RemoteDepEngine(fabric.engine(rank))
        ctx = parsec_tpu.Context(nb_cores=1, comm=eng, enable_tpu=False)
        try:
            coll = TwoDimBlockCyclic(2 * mb, mb, mb, mb, P=2, Q=1,
                                     nodes=2, rank=rank, dtype=np.float64)
            coll.name = "descX"
            coll.from_numpy(A0.copy())
            tp = ptg.compile_jdf(PREFETCH_JDF, name="prefetch_jdf").new(
                descX=coll, rank=rank, nb_ranks=nb_ranks)
            if rank == 1:
                # hold registration: rank 0's activation must arrive
                # FIRST and be buffered as an early activation
                deadline = time.time() + 60
                while time.time() < deadline \
                        and not eng._early_activations:
                    eng.ce.progress()
                    time.sleep(0.001)
                assert eng._early_activations, \
                    "activation never buffered ahead of registration"
                assert eng.stats["prefetch_gets"] == 1, eng.stats
                # let the prefetched payload land before registering,
                # so the hit is the already-done flavor
                while time.time() < deadline and not any(
                        r.done for r in eng._prefetched_gets.values()):
                    eng.ce.progress()
                    time.sleep(0.001)
                assert any(r.done
                           for r in eng._prefetched_gets.values())
            ctx.add_taskpool(tp)
            ctx.wait()
            stats = dict(eng.stats)
            out = (np.asarray(coll.data_of(1, 0).sync_to_host().payload)
                   if rank == 1 else None)
            return stats, out
        finally:
            ctx.fini()

    results, _fabric = spmd(nb_ranks, rank_fn, timeout=120)
    stats1, out1 = results[1]
    assert stats1["prefetch_gets"] == 1
    assert stats1["prefetch_hits"] == 1
    assert stats1["prefetch_misses"] == 0
    assert stats1["prefetch_cancels"] == 0
    assert results[0][0]["prefetch_gets"] == 0   # rank 0 never buffered
    np.testing.assert_allclose(out1, (A0[:mb] + 1.0) * 2.0, rtol=1e-12)


def test_prefetch_budget_zero_counts_miss():
    """With comm_prefetch_inflight=0 nothing is prefetched and nothing
    is counted — the off switch restores the pre-overlap behavior."""
    nb_ranks, mb = 2, 64
    A0 = np.random.RandomState(4).rand(2 * mb, mb)

    def rank_fn(rank, fabric):
        eng = RemoteDepEngine(fabric.engine(rank))
        ctx = parsec_tpu.Context(nb_cores=1, comm=eng, enable_tpu=False)
        try:
            coll = TwoDimBlockCyclic(2 * mb, mb, mb, mb, P=2, Q=1,
                                     nodes=2, rank=rank, dtype=np.float64)
            coll.name = "descX"
            coll.from_numpy(A0.copy())
            tp = ptg.compile_jdf(PREFETCH_JDF, name="prefetch_jdf").new(
                descX=coll, rank=rank, nb_ranks=nb_ranks)
            if rank == 1:
                deadline = time.time() + 60
                while time.time() < deadline \
                        and not eng._early_activations:
                    eng.ce.progress()
                    time.sleep(0.001)
                assert eng._early_activations
            ctx.add_taskpool(tp)
            ctx.wait()
            return dict(eng.stats)
        finally:
            ctx.fini()

    with params.cmdline_override("comm_prefetch_inflight", "0"):
        results, _fabric = spmd(nb_ranks, rank_fn, timeout=120)
    stats1 = results[1]
    assert stats1["prefetch_gets"] == 0
    assert stats1["prefetch_hits"] == 0
    # budget 0 = feature off: not even a miss is charged
    assert stats1["prefetch_misses"] == 0


def test_prefetch_late_reply_after_cancel_releases_budget_once():
    """A cancel (peer death / fini) racing a GET reply already sitting
    in the receive queue must release the budget slot exactly ONCE —
    a double decrement would let _plan_get_prefetch_locked admit more
    than comm_prefetch_inflight concurrent prefetches forever after."""
    from parsec_tpu.comm import LocalFabric
    from parsec_tpu.comm.remote_dep import _PrefetchedGet

    fabric = LocalFabric(2)
    eng = RemoteDepEngine(fabric.engine(1))
    captured = []
    eng._timed_get = lambda peer, handle, cb: captured.append(cb)
    key = (0, 7)
    with eng._lock:
        eng._prefetched_gets[key] = _PrefetchedGet()
        eng._prefetch_inflight += 1
    eng._issue_get_prefetch(*key)
    assert captured and eng._prefetch_inflight == 1
    eng._cancel_prefetches(0)            # the cancel releases the slot
    assert eng._prefetch_inflight == 0
    assert eng.stats["prefetch_cancels"] == 1
    captured[0](np.zeros(1))             # late reply: record is gone
    assert eng._prefetch_inflight == 0   # NOT -1


def test_prefetch_issue_failure_falls_back_to_latched_delivery():
    """If the prefetch GET fails to issue AFTER a replayed delivery
    already latched onto the record (set rec.cb, issued no GET of its
    own), the cleanup must not strand that delivery — it falls back to
    a plain GET for the latched callback instead of raising."""
    from parsec_tpu.comm import LocalFabric
    from parsec_tpu.comm.remote_dep import _PrefetchedGet

    fabric = LocalFabric(2)
    eng = RemoteDepEngine(fabric.engine(1))
    calls = []

    def timed_get(peer, handle, cb):
        calls.append(cb)
        if len(calls) == 1:
            raise RuntimeError("transport burp")

    eng._timed_get = timed_get
    key = (0, 9)
    rec = _PrefetchedGet()
    delivered = []
    rec.cb = delivered.append            # the replayed delivery's hook
    with eng._lock:
        eng._prefetched_gets[key] = rec
        eng._prefetch_inflight += 1
    eng._issue_get_prefetch(*key)        # must NOT raise: falls back
    assert len(calls) == 2 and calls[1] is rec.cb
    assert eng._prefetch_inflight == 0
    assert key not in eng._prefetched_gets
    assert eng.stats["prefetch_cancels"] == 1


# --------------------------------------------------------------------- #
# the live overlap tracker                                              #
# --------------------------------------------------------------------- #
def test_overlap_tracker_interval_algebra():
    from parsec_tpu.obs import OverlapTracker
    tr = OverlapTracker()
    # zero comm: perfect overlap by definition (gate-safe)
    assert tr.snapshot()["overlap_fraction"] == 1.0
    tr.note("compute", 0, 100_000)            # [0, 100] us
    assert tr.snapshot()["overlap_fraction"] == 1.0
    tr.note("comm", 50_000, 150_000)          # [50, 150] us: half hidden
    snap = tr.snapshot()
    assert snap["comm_us"] == pytest.approx(100.0)
    assert snap["overlap_fraction"] == pytest.approx(0.5)
    assert tr.exposed_us() == pytest.approx(50.0)
    tr.note("compute", 100_000, 150_000)      # cover the rest
    assert tr.fraction() == pytest.approx(1.0)


def test_overlap_tracker_coalesces_bounded():
    from parsec_tpu.obs import OverlapTracker
    tr = OverlapTracker()
    for i in range(3 * tr.COALESCE_AT):
        tr.note("comm", 1000 * i, 1000 * i + 500)
    assert len(tr._iv["comm"]) <= 2 * tr.COALESCE_AT
    # nothing lost to the coalescing
    assert tr.snapshot()["comm_us"] == pytest.approx(
        3 * tr.COALESCE_AT * 0.5)


# --------------------------------------------------------------------- #
# obs_report --gate-overlap (satellite)                                 #
# --------------------------------------------------------------------- #
def test_obs_report_gate_overlap(tmp_path, capsys):
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import obs_report

    def doc(events):
        return {"traceEvents": events, "metadata": {}}

    exposed = [
        {"name": "exec:K", "ph": "X", "pid": 0, "tid": 0, "ts": 0,
         "dur": 100.0, "args": {"task": "K(0)"}},
        {"name": "comm:get", "ph": "X", "pid": 0, "tid": 9, "ts": 200.0,
         "dur": 100.0},
    ]
    p_bad = tmp_path / "bad.trace.json"
    p_bad.write_text(__import__("json").dumps(doc(exposed)))
    assert obs_report.main([str(p_bad), "--gate-overlap", "0.5"]) == 2
    # zero-comm rank reports 1.0 and passes any gate
    p_ok = tmp_path / "ok.trace.json"
    p_ok.write_text(__import__("json").dumps(doc(exposed[:1])))
    assert obs_report.main([str(p_ok), "--gate-overlap", "0.99"]) == 0
    capsys.readouterr()
