"""ISSUE 19 — the xfer/ redistribution planner and loopback transport.

Covers:
- plan determinism: the schedule is a pure function of the two
  distributions (byte-identical across repeated builds and across
  independently constructed geometry objects), with golden structure
  for the canonical 4->2, 1x4->2x2, and 4->1 reshards;
- coalescing: one Transfer per cross-rank (src, dst) pair, so rounds
  and transfers stay strictly below the per-tile GET storm count;
- execution: knob-gated redistribute() fast path is bit-identical to
  the classic DTD pool, repeated runs byte-identical, digest handshake
  asserted across ranks (and a diverging plan fails LOUDLY);
- the in-process loopback transfer backend that un-skips the
  jax.experimental.transfer tests on CPU-only builds.
"""
import threading

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu.collections import TwoDimBlockCyclic
from parsec_tpu.collections.redistribute import redistribute
from parsec_tpu.comm import RemoteDepEngine
from parsec_tpu.utils.params import params
from parsec_tpu.xfer import build_plan, run_redistribution
from conftest import spmd_tcp
from test_comm_multirank import spmd


def _grid(lm, ln, mb, nb, P, Q, nodes, rank=0):
    return TwoDimBlockCyclic(lm, ln, mb, nb, P=P, Q=Q,
                             nodes=nodes, rank=rank, dtype=np.float64)


# --------------------------------------------------------------------- #
# plan construction                                                     #
# --------------------------------------------------------------------- #
def test_plan_golden_4_to_2():
    """P=4 -> P=2 row-cyclic reshard of a 4x4 tile grid: rows 0/1 stay
    local, rows 2/3 each coalesce into ONE transfer, and both pairs
    share the single (d - s) % 4 == 2 round."""
    src = _grid(8, 8, 2, 2, P=4, Q=1, nodes=4)
    tgt = _grid(8, 8, 2, 2, P=2, Q=1, nodes=4)
    plan = build_plan(src, tgt)
    assert plan.nb_ranks == 4
    assert len(plan.local) == 8            # tile rows 0 and 1
    assert plan.n_rounds == 1
    assert plan.n_transfers == 2           # (2->0) and (3->1), coalesced
    assert plan.tile_moves == 8
    (rnd,) = plan.rounds
    assert [(t.src, t.dst, len(t.tiles)) for t in rnd] == \
        [(2, 0, 4), (3, 1, 4)]


def test_plan_golden_1x4_to_2x2():
    """1x4 -> 2x2 grid flip: every coord whose owners differ moves,
    bucketed per (src, dst) pair — strictly fewer transfers than the
    per-tile storm would pay."""
    src = _grid(8, 8, 2, 2, P=1, Q=4, nodes=4)
    tgt = _grid(8, 8, 2, 2, P=2, Q=2, nodes=4)
    plan = build_plan(src, tgt)
    moved = plan.tile_moves
    assert moved + len(plan.local) == 16
    assert moved > 0
    assert plan.n_transfers < moved        # coalescing bought something
    for rnd in plan.rounds:
        # alltoall shape: within a round every sender/receiver is unique
        assert len({t.src for t in rnd}) == len(rnd)
        assert len({t.dst for t in rnd}) == len(rnd)
        for t in rnd:
            assert t.tiles == tuple(sorted(t.tiles))


def test_plan_golden_4_to_1():
    """Gather: P=4 -> P=1 concentrates everything on rank 0 — three
    coalesced transfers, one per source, spread over three rounds."""
    src = _grid(8, 8, 2, 2, P=4, Q=1, nodes=4)
    tgt = _grid(8, 8, 2, 2, P=1, Q=1, nodes=4)
    plan = build_plan(src, tgt)
    assert len(plan.local) == 4
    assert plan.n_transfers == 3
    assert plan.n_rounds == 3
    assert sorted((t.src, t.dst) for rnd in plan.rounds for t in rnd) \
        == [(1, 0), (2, 0), (3, 0)]


def test_plan_pure_function_of_distributions():
    """Two independently constructed geometry pairs produce
    byte-identical plans (and digests) — across ANY viewing rank: the
    schedule depends on the distributions, never on runtime state."""
    mk = lambda r: (_grid(12, 12, 3, 3, P=4, Q=1, nodes=4, rank=r),
                    _grid(12, 12, 3, 3, P=2, Q=2, nodes=4, rank=r))
    plans = [build_plan(*mk(r)) for r in range(4)] + [build_plan(*mk(0))]
    assert len({p.digest() for p in plans}) == 1
    assert all(p == plans[0] for p in plans)


# --------------------------------------------------------------------- #
# execution                                                             #
# --------------------------------------------------------------------- #
def _run_planned_reshard(nb_ranks, src_np, runs=1):
    """Knob-gated redistribute() on a whole-matrix reshard; returns
    (per-rank taskpool surrogates, assembled matrices, digests).
    24x24 over 3x3 tiles = an 8x8 tile grid, so every cross-rank
    (src, dst) pair coalesces SEVERAL tiles."""
    lm = ln = 24

    def rank_fn(rank, fabric):
        eng = RemoteDepEngine(fabric.engine(rank))
        ctx = parsec_tpu.Context(nb_cores=1, comm=eng, enable_tpu=False)
        try:
            outs = []
            for _ in range(runs):
                Y = _grid(lm, ln, 3, 3, P=nb_ranks, Q=1,
                          nodes=nb_ranks, rank=rank).from_numpy(src_np)
                T = _grid(lm, ln, 3, 3, P=1, Q=nb_ranks,
                          nodes=nb_ranks, rank=rank).from_numpy(
                              np.zeros((lm, ln)))
                tp = redistribute(Y, T, lm, ln, context=ctx)
                tiles = {c: np.array(T.tile(*c)) for c in T.local_tiles()}
                outs.append((tp, tiles))
            return outs
        finally:
            ctx.fini()

    results, _ = spmd(nb_ranks, rank_fn)
    assembled = []
    for run in range(runs):
        got = np.zeros((lm, ln))
        for r in range(nb_ranks):
            for (m, n), arr in results[r][run][1].items():
                got[m * 3:(m + 1) * 3, n * 3:(n + 1) * 3] = arr
        assembled.append(got)
    return results, assembled


def test_planned_redistribute_bit_identical_and_beats_storm():
    """xfer_collective_redist: the fast path must (a) deliver the
    bit-identical matrix, (b) return the planner surrogate whose round
    count is strictly below the per-tile move count (the GET storm's
    transfer count), (c) agree on the digest across every rank, and
    (d) stay byte-identical across repeated runs."""
    src_np = np.random.RandomState(7).rand(24, 24)
    params.set_cmdline("xfer_collective_redist", "1")
    try:
        results, assembled = _run_planned_reshard(4, src_np, runs=2)
    finally:
        params.unset_cmdline("xfer_collective_redist")
    for got in assembled:
        np.testing.assert_array_equal(got, src_np)
    digests = set()
    for r in range(4):
        for tp, _tiles in results[r]:
            assert hasattr(tp, "plan_digest"), \
                "knob set: planner surrogate expected, got DTD pool"
            assert tp.wire_lossless is True
            assert tp.redist_rounds < tp.redist_tile_moves
            assert tp.redist_transfers < tp.redist_tile_moves
            assert tp.redist_bytes > 0
            digests.add(tp.plan_digest)
    assert len(digests) == 1, digests


def test_planned_redistribute_knob_unset_keeps_dtd_pool():
    """Inertness: without the knob the classic DTD taskpool runs (no
    planner surface on the returned pool) and the result is identical."""
    src_np = np.random.RandomState(8).rand(24, 24)
    results, assembled = _run_planned_reshard(2, src_np)
    np.testing.assert_array_equal(assembled[0], src_np)
    for r in range(2):
        tp, _tiles = results[r][0]
        assert not hasattr(tp, "plan_digest")


def test_plan_digest_divergence_fails_loudly():
    """A rank whose target distribution disagrees must die in the
    digest handshake — never deadlock in a half-joined round."""
    nb = 2

    def rank_fn(rank, fabric):
        ce = fabric.engine(rank)
        src = _grid(8, 8, 2, 2, P=nb, Q=1, nodes=nb, rank=rank)
        src.from_numpy(np.zeros((8, 8)))
        # rank 1 flips the grid: plans diverge
        tgt = _grid(8, 8, 2, 2, P=1, Q=nb, nodes=nb, rank=rank) \
            if rank == 0 else _grid(8, 8, 2, 2, P=nb, Q=1,
                                    nodes=nb, rank=rank)
        tgt.from_numpy(np.zeros((8, 8)))
        run_redistribution(src, tgt, ce, timeout=30.0)

    with pytest.raises(RuntimeError, match="diverges"):
        spmd(nb, rank_fn)


def test_run_redistribution_bumps_round_gauge():
    """REDIST_ROUNDS: every executed plan adds its round count to the
    engine-owned dplane_stats the obs gauges poll."""
    nb = 2
    src_np = np.random.RandomState(9).rand(8, 8)

    def rank_fn(rank, fabric):
        ce = fabric.engine(rank)
        src = _grid(8, 8, 2, 2, P=nb, Q=1, nodes=nb,
                    rank=rank).from_numpy(src_np)
        tgt = _grid(8, 8, 2, 2, P=1, Q=nb, nodes=nb,
                    rank=rank).from_numpy(np.zeros((8, 8)))
        tp = run_redistribution(src, tgt, ce, timeout=30.0)
        return tp.redist_rounds, dict(ce.dplane_stats)

    results, _ = spmd(nb, rank_fn)
    for rounds, stats in results:
        assert rounds >= 1
        assert stats["redist_rounds"] == rounds


# --------------------------------------------------------------------- #
# the same reshard three ways over real TCP engines: per-tile storm,    #
# planned rounds, planned + device plane (bytes and counts, no clock)   #
# --------------------------------------------------------------------- #
def _tcp_reshard(knobs, attach_plane=False, n=64, tile=8, ranks=4):
    """A whole-matrix P x 1 -> 1 x Q reshard of an n x n f64 matrix
    over loopback TCP engines under ``knobs``; (assembled target, host
    wire bytes summed over ranks, per-rank pools, per-rank
    ``dplane_stats``)."""
    src_np = np.random.RandomState(19).rand(n, n)
    barrier = threading.Barrier(ranks)

    def rank_fn(r, ce):
        ctx = parsec_tpu.Context(nb_cores=1, comm=RemoteDepEngine(ce),
                                 enable_tpu=False)
        try:
            if attach_plane:
                from parsec_tpu.comm.xfer import DeviceDataPlane
                DeviceDataPlane(ce).exchange(timeout=60.0)
            Y = _grid(n, n, tile, tile, P=ranks, Q=1, nodes=ranks,
                      rank=r).from_numpy(src_np)
            T = _grid(n, n, tile, tile, P=1, Q=ranks, nodes=ranks,
                      rank=r).from_numpy(np.zeros((n, n)))
            barrier.wait(60)
            b0 = ce.fabric.bytes_count
            tp = redistribute(Y, T, n, n, context=ctx)
            barrier.wait(60)   # both directions fully flushed
            wire_bytes = ce.fabric.bytes_count - b0
            owned = {c: np.array(T.tile(*c)) for c in T.local_tiles()}
            return wire_bytes, tp, dict(ce.dplane_stats), owned
        finally:
            ctx.fini()

    results = spmd_tcp(ranks, rank_fn, knobs)
    got = np.zeros((n, n))
    for _b, _tp, _dp, owned in results:
        for (m, k), t in owned.items():
            got[m * tile:(m + 1) * tile, k * tile:(k + 1) * tile] = t
    return (got, src_np, sum(r[0] for r in results),
            [r[1] for r in results], [r[2] for r in results])


def test_tcp_reshard_planned_and_dplane_shed_host_wire_bytes():
    """Every leg lands the BIT-IDENTICAL matrix; the planned rounds
    move strictly fewer host-TCP bytes than the per-tile GET storm, in
    fewer rounds and transfers than tile moves; with ``xfer_dplane``
    the whole cross-rank payload leaves the session wire and what is
    left on it (descriptors, acks) is below the planned leg's."""
    n, tile, ranks = 64, 8, 4
    plan = build_plan(_grid(n, n, tile, tile, P=ranks, Q=1, nodes=ranks),
                      _grid(n, n, tile, tile, P=1, Q=ranks, nodes=ranks))
    moves = plan.tile_moves
    storm, src, storm_b, _tps, storm_dp = _tcp_reshard({})
    planned, _src, planned_b, tps, _dp = _tcp_reshard(
        {"xfer_collective_redist": "1"})
    dplane, _src, dplane_b, _tps, dp = _tcp_reshard(
        {"xfer_collective_redist": "1", "xfer_dplane": "1",
         "xfer_backend": "loopback"}, attach_plane=True)
    for got in (storm, planned, dplane):
        np.testing.assert_array_equal(got, src)
    assert 0 < planned_b < storm_b, (planned_b, storm_b)
    assert all(0 < tp.redist_rounds < moves for tp in tps)
    assert all(0 < tp.redist_transfers < moves for tp in tps)
    assert sum(d["dplane_xfers"] for d in storm_dp) == 0
    assert sum(d["dplane_xfers"] for d in dp) > 0
    assert sum(d["dplane_bytes"] for d in dp) == moves * tile * tile * 8
    assert 0 < dplane_b < planned_b, (dplane_b, planned_b)


def test_wire_capture_dplane_bit_identity():
    """The frame-level differential (tests/wire_capture.py, leg G):
    toward a peer that never advertised "dp", an ``xfer_dplane``
    sender's data frames are BIT-IDENTICAL to the knob-unset run."""
    from wire_capture import capture_identity

    out = capture_identity()
    assert out["trace_frames_captured"] > 0
    assert out["dplane_mixed_version_bit_identical"]


# --------------------------------------------------------------------- #
# loopback transfer backend                                             #
# --------------------------------------------------------------------- #
def test_loopback_roundtrip_and_one_pull_contract():
    pytest.importorskip("jax")
    import jax
    from parsec_tpu.xfer.loopback import LoopbackTransferServer
    a = LoopbackTransferServer("127.0.0.1:0")
    b = LoopbackTransferServer("127.0.0.1:0")
    try:
        arr = np.arange(24, dtype=np.float32).reshape(4, 6)
        a.await_pull(77, [arr])
        conn = b.connect(a.address())
        spec = jax.ShapeDtypeStruct(arr.shape, arr.dtype)
        (out,) = conn.pull(77, [spec])
        np.testing.assert_array_equal(np.asarray(out), arr)
        # pop-on-serve: a parked buffer serves exactly one pull
        with pytest.raises(KeyError):
            conn.pull(77, [spec])
        with pytest.raises(KeyError):
            conn.pull(12345, [spec])   # never parked
    finally:
        a.close()
        b.close()


def test_loopback_concurrent_pulls():
    """Many uuids pulled concurrently over one connection (the lock
    serializes round-trips, so interleaved threads stay correct)."""
    pytest.importorskip("jax")
    import jax
    from parsec_tpu.xfer.loopback import LoopbackTransferServer
    a = LoopbackTransferServer("127.0.0.1:0")
    b = LoopbackTransferServer("127.0.0.1:0")
    try:
        arrs = {u: np.random.RandomState(u).rand(32).astype(np.float32)
                for u in range(1, 9)}
        for u, arr in arrs.items():
            a.await_pull(u, [arr])
        conn = b.connect(a.address())
        outs, errs = {}, []

        def puller(u):
            try:
                spec = jax.ShapeDtypeStruct((32,), np.float32)
                outs[u] = np.asarray(conn.pull(u, [spec])[0])
            except BaseException as e:
                errs.append(e)

        ts = [threading.Thread(target=puller, args=(u,)) for u in arrs]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        assert not errs, errs
        for u, arr in arrs.items():
            np.testing.assert_array_equal(outs[u], arr)
    finally:
        a.close()
        b.close()


def test_backend_resolution():
    from parsec_tpu.comm.xfer import _resolve_backend
    mod, name = _resolve_backend("loopback")
    assert name == "loopback"
    mod_auto, name_auto = _resolve_backend("auto")
    try:
        from jax.experimental import transfer  # noqa: F401
        assert name_auto == "native"
    except ImportError:
        assert name_auto == "loopback"
        with pytest.raises(ImportError):
            _resolve_backend("native")
    with pytest.raises(ValueError):
        _resolve_backend("dcn")
