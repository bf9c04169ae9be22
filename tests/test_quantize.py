"""Quantized wire codecs + reduced-precision collectives (ISSUE 14).

Three layers under test:

- the error-feedback machinery (parallel/mesh.py): an iterative
  all-reduce whose contributions fall below the quantization quantum
  LOSES them forever without feedback (100% drift) and converges with
  it — the EQuARX recipe, the acceptance-gate differential;
- the reduced-precision collective lane (``wave_reduce_dtype`` on
  dsl/ptg/wave_dist._CollectiveLane): contributions quantize at the
  boundary through the SAME codec the wire uses, full-precision when
  the knob is unset (bit-for-bit differential against the plain lane);
- per-flow eligibility (comm/remote_dep.py): only float tile payloads
  quantize; pools that declare ``wire_lossless`` (checkpoint-reshard
  redistribution) never do.
"""
import functools

import numpy as np
import pytest

import parsec_tpu
from parsec_tpu.collections import TwoDimBlockCyclic, redistribute
from parsec_tpu.comm import wire
from parsec_tpu.comm.remote_dep import RemoteDepEngine
from parsec_tpu.dsl import ptg
from parsec_tpu.ops import dpotrf_taskpool, make_spd
from parsec_tpu.parallel.mesh import (ErrorFeedback, reduced_precision_sum,
                                      two_level_allreduce)
from parsec_tpu.utils.params import params

from conftest import spmd_tcp
from test_comm_multirank import spmd
from test_wave_dist import _gather_owned


# --------------------------------------------------------------------- #
# error feedback (the EQuARX differential)                              #
# --------------------------------------------------------------------- #
def test_error_feedback_converges_iterative_allreduce():
    """Contributions carry one large element (pinning the int8 block
    scale) plus many sub-quantum small ones. Without error feedback
    the small signal quantizes to zero EVERY round — the accumulated
    reduction diverges from the truth by 100% of it, forever. With
    feedback the residual accumulates until it crosses the quantum and
    is emitted: the total converges to within one quantum."""
    big = np.zeros(wire.QUANT_BLOCK, np.float32)
    big[0] = 100.0                       # scale = 100/127 per block
    small = np.full(wire.QUANT_BLOCK, 0.01, np.float32)
    small[0] = 0.0                       # 0.01 << quantum (~0.39)
    contrib = big + small
    K = 500
    ef = ErrorFeedback()
    tot_no = np.zeros_like(contrib)
    tot_ef = np.zeros_like(contrib)
    for _ in range(K):
        tot_no += wire.qdq_array(contrib, "qint8")
        tot_ef += ef.compensate("grad", contrib, "qint8",
                                wire.qdq_array)
    true = contrib * K
    rel_no = float(np.abs(tot_no[1:] - true[1:]).max() / true[1])
    rel_ef = float(np.abs(tot_ef[1:] - true[1:]).max() / true[1])
    assert rel_no > 0.99, rel_no     # diverged: the signal is GONE
    assert rel_ef < 0.1, rel_ef      # converged: within one quantum
    assert ef.keys() == ["grad"]


def test_error_feedback_shape_change_starts_fresh():
    ef = ErrorFeedback()
    a = np.full(8, 0.3, np.float32)
    ef.compensate("k", a, "qbf16", wire.qdq_array)
    # a different shape under the same key must not fold the stale
    # residual (it names a different buffer now)
    b = np.full(16, 0.3, np.float32)
    out = ef.compensate("k", b, "qbf16", wire.qdq_array)
    np.testing.assert_array_equal(out, wire.qdq_array(b, "qbf16"))
    ef.reset("k")
    assert ef.keys() == []


def test_reduced_precision_sum_unset_is_exact():
    rng = np.random.RandomState(0)
    xs = [rng.randn(257).astype(np.float64) for _ in range(5)]
    exact = np.zeros_like(xs[0])
    for x in xs:
        exact = exact + x
    out = reduced_precision_sum(xs, None)
    np.testing.assert_array_equal(out, exact)   # bit-for-bit
    np.testing.assert_array_equal(reduced_precision_sum(xs, ""), exact)


def test_reduced_precision_sum_quantizes_each_contribution():
    rng = np.random.RandomState(1)
    xs = [rng.randn(1000).astype(np.float32) for _ in range(3)]
    out = reduced_precision_sum(xs, "bf16")
    manual = sum(wire.qdq_array(x, "qbf16") for x in xs)
    np.testing.assert_array_equal(out, manual)
    exact = sum(xs)
    rel = np.abs(out - exact).max() / np.abs(exact).max()
    assert 0 < rel < 0.02, rel


def test_two_level_allreduce_boundary_quantization():
    """Level 1 (intra-group) stays full precision; only each group's
    boundary partial quantizes — the two-level win: one quantization
    per GROUP, not per contributor."""
    rng = np.random.RandomState(2)
    xs = [rng.randn(512).astype(np.float32) for _ in range(4)]
    exact = (xs[0] + xs[1]) + (xs[2] + xs[3])
    lossless = two_level_allreduce(xs, 2, None)
    np.testing.assert_array_equal(lossless, exact)
    q = two_level_allreduce(xs, 2, "int8")
    manual = (wire.qdq_array(xs[0] + xs[1], "qint8")
              + wire.qdq_array(xs[2] + xs[3], "qint8"))
    np.testing.assert_array_equal(q, manual)
    # error feedback across repeated calls of the same logical buffer
    ef = ErrorFeedback()
    t1 = two_level_allreduce(xs, 2, "int8", feedback=ef, key="g")
    np.testing.assert_array_equal(t1, q)   # first round: no residual yet
    assert sorted(ef.keys()) == [("g", 0), ("g", 1)]
    t2 = two_level_allreduce(xs, 2, "int8", feedback=ef, key="g")
    assert not np.array_equal(t2, t1)      # residual folded in


# --------------------------------------------------------------------- #
# the collective lane under wave_reduce_dtype                           #
# --------------------------------------------------------------------- #
def _single_rank_lane(reduce_dtype):
    import threading
    from parsec_tpu.dsl.ptg.wave_dist import _CollectiveLane
    rdv = ({}, {}, threading.Condition())
    return _CollectiveLane("inproc", 1, 0, rendezvous=rdv,
                           reduce_dtype=reduce_dtype)


def test_lane_quantizes_contribution_at_boundary():
    lane = _single_rank_lane("int8")
    x = np.random.RandomState(3).randn(4, 8, 8).astype(np.float32)
    out = np.asarray(lane.reduce(("p", 1, 0, 0), x))
    np.testing.assert_array_equal(out, wire.qdq_array(x, "qint8"))
    assert lane.quantized_reduces == 1


def test_lane_rejects_unknown_reduce_dtype():
    """A typo'd wave_reduce_dtype must fail LOUDLY (at lane/runner
    setup), never silently disable the lane under mode=auto."""
    with pytest.raises(ValueError):
        _single_rank_lane("fp16")


def test_lane_unset_keeps_full_precision():
    lane = _single_rank_lane("")
    assert lane._qcodec is None
    x = np.random.RandomState(4).randn(2, 8).astype(np.float32)
    out = np.asarray(lane.reduce(("p", 1, 0, 1), x))
    np.testing.assert_array_equal(out, x)   # bit-for-bit
    assert lane.quantized_reduces == 0


def test_lane_error_feedback_needs_stable_key():
    """Without ``fb_key`` the lane quantizes WITHOUT feedback (wave
    broadcast steps carry different tiles every wave — folding one
    wave's residual into the next would corrupt unrelated data); with
    a stable key the residual carries into the next contribution."""
    lane = _single_rank_lane("int8")
    big = np.zeros((1, wire.QUANT_BLOCK), np.float32)
    big[0, 0] = 100.0
    c = big.copy()
    c[0, 1] = 0.01    # sub-quantum
    out1 = np.asarray(lane.reduce(("p", 1, 0, 0), c))
    out2 = np.asarray(lane.reduce(("p", 1, 1, 0), c))
    np.testing.assert_array_equal(out1, out2)   # no feedback: identical
    tot = np.zeros_like(c)
    for w in range(60):
        tot += np.asarray(lane.reduce(("q", 1, w, 0), c, fb_key="buf"))
    assert tot[0, 1] > 0, "feedback never emitted the accumulated signal"


def test_lane_two_level_reduction_engages_and_differs_from_flat():
    """``xfer_collective_redist`` (ISSUE 19): deposits stay FULL
    precision and the issuer reduces hierarchically — full-precision
    partial sums inside each ``xfer_group_size`` group, ONE jit-native
    qdq per group at the boundary. Crafted input where flat
    per-contribution quantize and two-level round DIFFERENTLY:
    256 + 1 accumulates exactly inside a group, but 257 is not a bf16
    value, so the boundary hop rounds each partial to 256 (total 512)
    while the flat path delivers 514. Every member picks up the
    bit-identical replicated result; TWO_LEVEL_REDUCES accounting
    fires once per member and the per-contribution counter stays 0."""
    pytest.importorskip("jax")
    import threading
    from parsec_tpu.dsl.ptg.wave_dist import _CollectiveLane
    n = 4
    contribs = [np.full((2, 8), v, np.float32)
                for v in (256.0, 1.0, 256.0, 1.0)]
    params.set_cmdline("xfer_collective_redist", "1")
    params.set_cmdline("xfer_group_size", "2")
    try:
        rdv = ({}, {}, threading.Condition())
        efb = ErrorFeedback()
        stats = [{"two_level_reduces": 0} for _ in range(n)]
        lanes = [_CollectiveLane("inproc", n, r, rendezvous=rdv,
                                 reduce_dtype="bf16",
                                 shared_feedback=efb, stats=stats[r])
                 for r in range(n)]
        outs = [None] * n
        errs = []

        def run(r):
            try:
                outs[r] = np.asarray(
                    lanes[r].reduce(("p", 1, 0, 0), contribs[r]))
            except BaseException as e:
                errs.append(e)

        ts = [threading.Thread(target=run, args=(r,)) for r in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not errs, errs
    finally:
        params.unset_cmdline("xfer_collective_redist")
        params.unset_cmdline("xfer_group_size")
    exp = two_level_allreduce(contribs, 2, "bf16")
    flat = reduced_precision_sum(contribs, "bf16")
    assert not np.array_equal(exp, flat), "input must discriminate"
    for r in range(n):
        np.testing.assert_array_equal(outs[r], exp)
    assert all(ln.two_level_reduces == 1 for ln in lanes)
    assert all(ln.quantized_reduces == 0 for ln in lanes)
    assert all(s["two_level_reduces"] == 1 for s in stats)


def test_lane_two_level_group_size_gates_engagement():
    """len(members) must EXCEED the group size for the hierarchy to
    buy anything — at group_size >= member count the lane keeps the
    flat per-contribution quantize (and its counter)."""
    pytest.importorskip("jax")
    import threading
    from parsec_tpu.dsl.ptg.wave_dist import _CollectiveLane
    params.set_cmdline("xfer_collective_redist", "1")
    params.set_cmdline("xfer_group_size", "4")
    try:
        rdv = ({}, {}, threading.Condition())
        lane = _CollectiveLane("inproc", 1, 0, rendezvous=rdv,
                               reduce_dtype="bf16")
        x = np.full((2, 4), 256.0, np.float32) + 1.0
        out = np.asarray(lane.reduce(("p", 1, 0, 0), x))
        np.testing.assert_array_equal(out, wire.qdq_array(x, "qbf16"))
        assert lane.two_level_reduces == 0
        assert lane.quantized_reduces == 1
    finally:
        params.unset_cmdline("xfer_collective_redist")
        params.unset_cmdline("xfer_group_size")


def test_wave_reduce_dtype_dpotrf_within_bound(nb_ranks=4):
    """End to end: the 4-rank row-cyclic dist-wave dpotrf whose panel
    broadcasts ride the compiled collective lane, with the lane
    quantizing at bf16 — the factor must stay within a declared
    residual bound of numpy cholesky (not bit-exact: the wire is lossy
    by contract), with quantized reduces really counted. The unset
    knob keeps today's bit-exact lane (covered by
    test_dist_wave_collective_lane_dpotrf_matches)."""
    n, nb = 256, 32
    M = make_spd(n, dtype=np.float64)

    def rank_fn(r, f):
        ce = f.engine(r)
        coll = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float64,
                                 P=nb_ranks, Q=1,
                                 nodes=nb_ranks, rank=r)
        coll.name = "descA"
        coll.from_numpy(M.copy())
        tp = dpotrf_taskpool(coll, rank=r, nb_ranks=nb_ranks)
        w = ptg.wave(tp, comm=ce)
        w.run()
        return w.stats, _gather_owned(coll, rank=r)

    params.set_cmdline("wave_dist_collective", "on")
    params.set_cmdline("wave_reduce_dtype", "bf16")
    try:
        results, _ = spmd(nb_ranks, rank_fn, timeout=180)
    finally:
        params.unset_cmdline("wave_dist_collective")
        params.unset_cmdline("wave_reduce_dtype")
    L = np.zeros((n, n))
    for (_st, owned) in results:
        for (m, k), t in owned.items():
            L[m * nb:(m + 1) * nb, k * nb:(k + 1) * nb] = t
    L = np.tril(L)
    stats = [st for (st, _o) in results]
    assert all(s["collective_reduce_dtype"] == "qbf16" for s in stats)
    assert sum(s["collective_quantized"] for s in stats) > 0, stats
    ref = np.linalg.cholesky(M)
    resid = np.abs(L - ref).max() / np.abs(ref).max()
    assert resid < 1e-2, resid   # lossy but bounded (measured ~1e-3)


# --------------------------------------------------------------------- #
# comm_quantize end to end: a 2-rank classic-runtime dpotrf over real   #
# loopback TCP, per wire codec (counts and bounds, no clock)            #
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _tcp_dpotrf(codec, n=256, nb=64, chunk_bytes=8192):
    """Factor, residual and per-rank wire counters of a 2-rank dpotrf
    whose 16 KB tiles ride the chunked lane under ``codec`` ("" =
    unset).  Cached: the lossless leg is every codec case's baseline."""
    ranks = 2
    M = make_spd(n, dtype=np.float32)

    def rank_fn(r, ce):
        ctx = parsec_tpu.Context(nb_cores=1, comm=RemoteDepEngine(ce))
        try:
            coll = TwoDimBlockCyclic(n, n, nb, nb, dtype=np.float32,
                                     P=ranks, Q=1, nodes=ranks, rank=r)
            coll.name = "descA"
            coll.from_numpy(M.copy())
            ctx.add_taskpool(dpotrf_taskpool(coll, rank=r, nb_ranks=ranks))
            ctx.wait()
            stats = {k: ce.wire_stats[k]
                     for k in ("chunk_bytes_sent", "bufs_quantized",
                               "bytes_prequant", "bytes_postquant")}
            stats["ratio"] = (ce.codec_ratio(1 - r, "q" + codec)
                              if codec else 1.0)
            return stats, _gather_owned(coll, rank=r)
        finally:
            ctx.fini()

    results = spmd_tcp(ranks, rank_fn, {
        "comm_chunk_bytes": str(chunk_bytes), "comm_quantize": codec,
        "comm_mesh_local": "0"})   # payloads must ride the wire
    L = np.zeros((n, n), np.float32)
    for _st, owned in results:
        for (m, k), t in owned.items():
            L[m * nb:(m + 1) * nb, k * nb:(k + 1) * nb] = t
    Lt = np.tril(L).astype(np.float64)
    resid = float(np.abs(Lt @ Lt.T - M).max() / np.abs(M).max())
    return L, resid, [st for st, _o in results]


def test_comm_quantize_unset_dpotrf_is_bit_identical_and_lossless():
    """Knob-unset differential: two lossless runs land BIT-IDENTICAL
    tiles, move their tiles on the chunked lane, and quantize nothing."""
    L_a, resid, stats = _tcp_dpotrf("")
    L_b, _resid, _stats = _tcp_dpotrf.__wrapped__("")   # a second run
    assert np.array_equal(L_a, L_b)
    assert resid < 1e-5
    assert sum(s["chunk_bytes_sent"] for s in stats) > 0
    assert all(s["bufs_quantized"] == 0 for s in stats), stats


@pytest.mark.parametrize("codec,bound", [("bf16", 1e-3), ("int8", 5e-3)])
def test_comm_quantize_dpotrf_fewer_bytes_within_declared_bound(codec,
                                                                bound):
    """Each codec engages on every link (buffers quantized, ratio > 1),
    moves STRICTLY fewer payload bytes than the lossless leg, and keeps
    the residual inside its DECLARED bound (measured 5e-5 / 3e-4)."""
    _L0, _r0, base = _tcp_dpotrf("")
    _L, resid, stats = _tcp_dpotrf(codec)
    assert all(s["bufs_quantized"] > 0 for s in stats), stats
    assert all(s["ratio"] > 1.0 for s in stats), stats
    assert all(s["bytes_postquant"] < s["bytes_prequant"] for s in stats)
    sent = sum(s["chunk_bytes_sent"] for s in stats)
    sent0 = sum(s["chunk_bytes_sent"] for s in base)
    assert 0 < sent < sent0, (sent, sent0)
    assert resid <= bound, (codec, resid)


# --------------------------------------------------------------------- #
# per-flow eligibility                                                  #
# --------------------------------------------------------------------- #
class _FakeTp:
    pass


def test_quantize_eligibility_per_flow():
    el = RemoteDepEngine._quantize_eligible
    tp = _FakeTp()
    assert el(tp, np.zeros(4, np.float32))
    assert el(tp, np.zeros(4, np.float64))
    assert not el(tp, np.zeros(4, np.int32))     # non-float: lossless
    assert not el(tp, None)                      # release-only
    lossless_tp = _FakeTp()
    lossless_tp.wire_lossless = True
    assert not el(lossless_tp, np.zeros(4, np.float64))


def test_redistribute_pool_is_wire_lossless(ctx):
    """Checkpoint-reshard restores ride redistribute(); its pool must
    mark itself lossless so reshard traffic NEVER quantizes whatever
    the knobs say — golden reshards stay bit-identical."""
    rng = np.random.RandomState(5)
    src = rng.rand(8, 8)
    Y = TwoDimBlockCyclic(8, 8, 4, 4,
                          dtype=np.float64).from_numpy(src)
    T = TwoDimBlockCyclic(8, 8, 2, 2,
                          dtype=np.float64).from_numpy(np.zeros((8, 8)))
    tp = redistribute(Y, T, 8, 8, context=ctx)
    assert getattr(tp, "wire_lossless", False) is True
    np.testing.assert_array_equal(T.to_numpy(), src)


def test_qdq_matches_wire_delivery_layout():
    """qdq_array is EXACTLY what a quantized wire transfer delivers:
    same codec functions, same block layout — asserted here so the
    lane and the wire can never round differently."""
    rng = np.random.RandomState(6)
    for dt, fmt in ((np.float64, "d"), (np.float32, "f")):
        arr = (rng.randn(1030) * 3).astype(dt)   # non-multiple of block
        for codec in wire.available_quant_codecs():
            enc = wire.quantize_buffer(
                memoryview(np.ascontiguousarray(arr)).cast("B"),
                fmt, codec)
            raw = wire.dequantize_buffer(enc)
            via_wire = np.frombuffer(raw, dtype=dt)
            np.testing.assert_array_equal(
                via_wire, wire.qdq_array(arr, codec))


def test_native_qdq_bit_parity_with_numpy():
    """The jit-native quantize hop (ISSUE 17): ``qdq_jax`` lowered
    through XLA must deliver BIT-FOR-BIT the values the numpy wire
    codec delivers — every dtype, every shape class (block multiples,
    remainders, multi-dim), every magnitude, all-zero blocks included
    (the 1/scale guard).  Without this, a ``native=True`` reduction
    would round differently from the wire and the lane/wire identity
    contract of ISSUE 14 would silently break."""
    pytest.importorskip("jax")
    from parsec_tpu.parallel.mesh import _qdq_native
    rng = np.random.RandomState(7)
    for codec in wire.available_quant_codecs():
        for dt in (np.float32, np.float64):
            for shape in ((7,), (512,), (513,), (64, 33), (3, 5, 7)):
                for scale in (1e-6, 1.0, 1e4):
                    x = (rng.randn(*shape) * scale).astype(dt)
                    a = wire.qdq_array(x, codec)
                    b = _qdq_native(x, codec)
                    assert a.dtype == b.dtype and a.shape == b.shape
                    np.testing.assert_array_equal(a, b)
        z = np.zeros(600, np.float32)   # zero-scale blocks
        np.testing.assert_array_equal(wire.qdq_array(z, codec),
                                      _qdq_native(z, codec))


def test_native_two_level_allreduce_bit_parity():
    """two_level_allreduce's DEFAULT boundary quantize is now the
    XLA-lowered native hop (ISSUE 19 satellite: no host-side numpy
    quantize left on the default path) — it must stay bit-identical to
    the eager wire codec (``native=False``), with and without error
    feedback across iterations (the residual carry must see the exact
    same quantized values, or feedback states diverge)."""
    pytest.importorskip("jax")
    rng = np.random.RandomState(8)
    shards = [rng.randn(300).astype(np.float32) for _ in range(8)]
    for rd in wire.available_quant_codecs():
        np.testing.assert_array_equal(
            two_level_allreduce(shards, 4, rd, native=False),
            two_level_allreduce(shards, 4, rd))
        fb_np, fb_jx = ErrorFeedback(), ErrorFeedback()
        for _ in range(3):
            r_np = two_level_allreduce(shards, 4, rd, feedback=fb_np,
                                       key="k", native=False)
            r_jx = two_level_allreduce(shards, 4, rd,
                                       feedback=fb_jx, key="k")
            np.testing.assert_array_equal(r_np, r_jx)
    # no codec: the native default must not disturb the exact sum
    np.testing.assert_array_equal(
        two_level_allreduce(shards, 4, None, native=False),
        two_level_allreduce(shards, 4, None))
