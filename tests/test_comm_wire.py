"""Wire fast-path tests (ISSUE 2): coalesced framing, chunked
pipelining, per-link compression, GET aggregation, and the adaptive
eager/rendezvous cutoff — plus framing robustness against partial
reads, mixed-version peers, and desync.

The loopback two-rank fixture is ``_engines`` (in-process TCP engines
over real sockets); the raw-socket fixture speaks the frame format
by hand to exercise receiver robustness.
"""
import pickle
import socket
import struct
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from parsec_tpu.comm import wire
from parsec_tpu.comm.tcp import TCPCommEngine, free_ports
from parsec_tpu.utils.params import params


def _engines(n=2, **knobs):
    ports = free_ports(n)
    eps = [("127.0.0.1", p) for p in ports]
    import concurrent.futures as cf
    with cf.ThreadPoolExecutor(n) as ex:
        return list(ex.map(lambda r: TCPCommEngine(r, eps, **knobs),
                           range(n)))


def _drain_until(eng, pred, timeout=15.0):
    deadline = time.time() + timeout
    while not pred() and time.time() < deadline:
        if not eng.progress():
            time.sleep(0.0005)
    assert pred(), "condition not reached before timeout"


def _raw_peer(engine, as_rank=1):
    """A hand-driven socket posing as ``as_rank`` toward ``engine``
    (handshake only — NO hello, i.e. a mixed-version peer)."""
    host, port = engine.endpoints[engine.rank]
    sock = socket.create_connection((host, port))
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.sendall(struct.pack("<I", as_rank))
    # wait until the engine registered us (its hello lands in our rx
    # buffer; we never parse it — a v1 peer wouldn't)
    deadline = time.time() + 10
    while as_rank not in engine._conns and time.time() < deadline:
        time.sleep(0.005)
    assert as_rank in engine._conns
    return sock


def _frame(body: bytes) -> bytes:
    return struct.pack("<Q", len(body)) + body


def _batch_frame(msgs):
    segs = []
    for (src, tag, payload) in msgs:
        bufs = []
        fr = pickle.dumps((src, tag, payload), protocol=5,
                          buffer_callback=bufs.append)
        segs.append(wire.pack_segment(fr, [b.raw() for b in bufs]))
    return _frame(b"".join(wire.pack_batch(segs)))


# ---------------------------------------------------------------------- #
# receiver robustness (raw-socket fixture)                               #
# ---------------------------------------------------------------------- #
def test_partial_frame_recv_reassembles():
    """A frame trickling in across many partial reads must reassemble
    byte-exactly (recv returning short is the TCP norm, not an edge)."""
    (e0,) = _engines(1)
    # widen the fixture: a 2-endpoint view so a fake rank 1 may dial in
    e0.endpoints.append(("127.0.0.1", 0))
    e0.fabric.nb_ranks = e0.nb_ranks = 2
    sock = _raw_peer(e0)
    try:
        got = []
        e0.tag_register(100, lambda src, p: got.append((src, p)))
        data = _batch_frame([(1, 100, {"x": 42,
                                       "arr": np.arange(5.0)})])
        for i in range(0, len(data), 7):     # 7-byte dribble
            sock.sendall(data[i:i + 7])
            time.sleep(0.001)
        _drain_until(e0, lambda: got)
        assert got[0][0] == 1 and got[0][1]["x"] == 42
        np.testing.assert_array_equal(got[0][1]["arr"], np.arange(5))
    finally:
        sock.close()
        e0.fini()


def test_multi_message_coalesced_frame_delivers_in_order():
    """One K_BATCH frame carrying several messages delivers each, in
    order, with out-of-band buffers correctly re-sliced."""
    (e0,) = _engines(1)
    e0.endpoints.append(("127.0.0.1", 0))
    e0.fabric.nb_ranks = e0.nb_ranks = 2
    sock = _raw_peer(e0)
    try:
        got = []
        e0.tag_register(77, lambda src, p: got.append(p))
        msgs = [(1, 77, {"i": i, "arr": np.full((4,), i, np.float32)})
                for i in range(5)]
        sock.sendall(_batch_frame(msgs))
        _drain_until(e0, lambda: len(got) == 5)
        assert [p["i"] for p in got] == list(range(5))
        np.testing.assert_array_equal(got[3]["arr"], np.full((4,), 3))
    finally:
        sock.close()
        e0.fini()


def test_unknown_frame_kind_marks_peer_dead():
    """Garbage after the length prefix is a desync: the receiver must
    fail LOUDLY (peer marked dead) instead of hanging both ranks."""
    (e0,) = _engines(1)
    e0.endpoints.append(("127.0.0.1", 0))
    e0.fabric.nb_ranks = e0.nb_ranks = 2
    sock = _raw_peer(e0)
    try:
        sock.sendall(_frame(b"\xfagarbage"))
        deadline = time.time() + 10
        while 1 not in e0.dead_peers and time.time() < deadline:
            time.sleep(0.005)
        assert 1 in e0.dead_peers
    finally:
        sock.close()
        e0.fini()


def test_goodbye_mid_chunked_transfer_is_a_failure():
    """A clean GOODBYE while a chunked transfer is incomplete is a
    protocol violation — the peer owes data."""
    (e0,) = _engines(1)
    e0.endpoints.append(("127.0.0.1", 0))
    e0.fabric.nb_ranks = e0.nb_ranks = 2
    sock = _raw_peer(e0)
    try:
        payload = np.zeros(1 << 16, np.float64)     # 512 KB, chunked
        bufs = []
        fr = pickle.dumps((1, 90, {"arr": payload}), protocol=5,
                          buffer_callback=bufs.append)
        v = bufs[0].raw()
        hdr = wire.pack_xfer_hdr(7, fr, [(True, v.nbytes, None)])
        sock.sendall(_frame(hdr))
        # one chunk of the announced buffer, then a "clean" goodbye
        sock.sendall(_frame(wire.pack_chunk_hdr(7, 0, 0)
                            + bytes(v[:1024])))
        sock.sendall(struct.pack("<Q", wire.GOODBYE))
        deadline = time.time() + 10
        while 1 not in e0.dead_peers and time.time() < deadline:
            time.sleep(0.005)
        assert 1 in e0.dead_peers
        assert 1 not in e0.finished_peers
    finally:
        sock.close()
        e0.fini()


# ---------------------------------------------------------------------- #
# chunked pipelining (engine pair)                                       #
# ---------------------------------------------------------------------- #
def test_chunked_buffer_reassembly_roundtrip():
    e0, e1 = _engines(2, chunk_bytes=1 << 16)
    try:
        big = np.random.RandomState(3).rand(1 << 19)      # 4 MB
        small = np.arange(7, dtype=np.int64)
        got = []
        e1.tag_register(200, lambda src, p: got.append(p))
        e0.send_am(1, 200, {"big": big, "small": small, "k": 9})
        _drain_until(e1, lambda: got)
        np.testing.assert_array_equal(got[0]["big"], big)
        np.testing.assert_array_equal(got[0]["small"], small)
        assert got[0]["k"] == 9
        assert e0.wire_stats["chunks_sent"] >= 64   # really chunked
        assert e0.wire_stats["msgs_chunked"] == 1
    finally:
        e0.fini()
        e1.fini()


def test_control_am_interleaves_with_bulk_payload():
    """The acceptance probe: a small control AM enqueued while a >= 4 MB
    payload is in flight must NOT wait behind it — its delivery
    interleaves between chunks and lands before the bulk message."""
    e0, e1 = _engines(2, chunk_bytes=1 << 16)
    try:
        order = []
        lat = {}
        e1.tag_register(300, lambda src, p: order.append("bulk"))

        def on_ctrl(src, p):
            order.append("ctrl")
            lat["ctrl_ms"] = (time.perf_counter() - p["t0"]) * 1e3

        e1.tag_register(301, on_ctrl)
        big = np.random.RandomState(0).rand(1 << 21)      # 16 MB
        e0.send_am(1, 300, {"arr": big})
        e0.send_am(1, 301, {"t0": time.perf_counter()})
        _drain_until(e1, lambda: len(order) == 2, timeout=60)
        assert order[0] == "ctrl", order       # overtook the bulk tile
        # bounded latency: the control AM waited for at most a chunk or
        # two, not the whole 16 MB drain (generous CI margin)
        assert lat["ctrl_ms"] < 2000, lat
    finally:
        e0.fini()
        e1.fini()


def test_bounded_send_buffer_backpressures_without_deadlock():
    """With a tiny send buffer, a burst of bulk messages must stall the
    sender (bounded memory) yet drain completely — and a message larger
    than the whole buffer is still admitted alone."""
    params.set_cmdline("comm_send_buffer_bytes", str(1 << 18))  # 256 KB
    try:
        e0, e1 = _engines(2, chunk_bytes=1 << 16)
    finally:
        params.unset_cmdline("comm_send_buffer_bytes")
    try:
        assert e0.send_buffer_bytes == 1 << 18
        got = []
        e1.tag_register(950, lambda src, p: got.append(p["i"]))
        rng = np.random.RandomState(9)
        payloads = [rng.rand(1 << 17) for _ in range(8)]   # 1 MB each
        for i, arr in enumerate(payloads):
            e0.send_am(1, 950, {"i": i, "arr": arr})       # > buffer
        _drain_until(e1, lambda: len(got) == 8, timeout=60)
        assert got == list(range(8))
        assert all(p.queued_bytes == 0 for p in e0._peers.values())
    finally:
        e0.fini()
        e1.fini()


def test_chunked_transfer_after_control_burst():
    """Regression: a burst of control AMs followed by a chunked payload
    (and more control traffic racing it) must deliver everything — the
    transfer header precedes its first chunk STRUCTURALLY (both ride
    the FIFO bulk lane), whatever the anti-starvation streak says."""
    e0, e1 = _engines(2, chunk_bytes=1 << 16)
    try:
        got, bulk = [], []
        e1.tag_register(900, lambda src, p: got.append(p))
        e1.tag_register(901, lambda src, p: bulk.append(p))
        for i in range(64):
            e0.send_am(1, 900, {"i": i})
        big = np.random.RandomState(5).rand(1 << 18)      # 2 MB
        e0.send_am(1, 901, {"arr": big})
        for i in range(64):
            e0.send_am(1, 900, {"i": 64 + i})
        _drain_until(e1, lambda: len(got) == 128 and bulk, timeout=60)
        np.testing.assert_array_equal(bulk[0]["arr"], big)
        assert 1 not in e0.dead_peers and 0 not in e1.dead_peers
    finally:
        e0.fini()
        e1.fini()


def test_mutable_bulk_payload_snapshots_at_enqueue():
    """A writable buffer on the chunked path is snapshotted when
    send_am returns (the historical copy-at-send contract): mutating it
    right after the call must not tear the bytes on the wire. Only
    read-only buffers (marked by the rendezvous/wave producers) ride
    zero-copy."""
    e0, e1 = _engines(2, chunk_bytes=1 << 16)
    try:
        got = []
        e1.tag_register(800, lambda src, p: got.append(p))
        big = np.ones(1 << 19)                 # 4 MB, writable
        e0.send_am(1, 800, {"arr": big})
        big[:] = -1.0                          # mutate immediately
        _drain_until(e1, lambda: got, timeout=60)
        np.testing.assert_array_equal(got[0]["arr"], np.ones(1 << 19))
    finally:
        e0.fini()
        e1.fini()


# ---------------------------------------------------------------------- #
# compression                                                            #
# ---------------------------------------------------------------------- #
def test_compressed_frame_roundtrip():
    """With the bandwidth threshold forced sky-high, compressible bulk
    traffic engages the negotiated codec after the first bandwidth
    sample and round-trips intact; the ratio gauge moves below 1."""
    e0, e1 = _engines(2, chunk_bytes=1 << 16,
                      compress_threshold_mbps=10 ** 7)
    try:
        deadline = time.time() + 10           # negotiation done first
        # _peer_to waits for the accept thread's registration: under
        # full-suite load the connection may not be in _peers yet
        peer = e0._peer_to(1)
        while peer.codec is None and time.time() < deadline:
            time.sleep(0.005)
        assert peer.codec is not None
        got = []
        e1.tag_register(400, lambda src, p: got.append(p))
        z = np.zeros(1 << 19)                 # 4 MB of zeros: compresses
        for rep in range(3):                  # rep 1 measures bw, later
            got.clear()                       # reps ride compressed
            e0.send_am(1, 400, {"arr": z, "rep": rep})
            _drain_until(e1, lambda: got, timeout=60)
            np.testing.assert_array_equal(got[0]["arr"], z)
        assert e0.wire_stats["frames_compressed"] > 0, e0.wire_stats
        ratio = e0.compress_ratio()
        assert ratio is not None and ratio < 0.5, ratio
    finally:
        e0.fini()
        e1.fini()


def test_mixed_version_peer_stays_uncompressed():
    """A peer that never advertised codecs (no HELLO — an older wire
    version) must never receive compressed frames, whatever the knobs
    say; traffic still round-trips."""
    e0, e1 = _engines(2, chunk_bytes=1 << 16,
                      compress_threshold_mbps=10 ** 7)
    try:
        # simulate the failed negotiation: as if peer 1's HELLO never
        # carried codecs we know. Wait for the real HELLO first — the
        # override must not be raced and re-negotiated by its arrival.
        deadline = time.time() + 10
        peer = e0._peer_to(1)      # waits for the accept registration
        while peer.codec is None and time.time() < deadline:
            time.sleep(0.005)
        assert peer.codec is not None
        peer.codec = None
        got = []
        e1.tag_register(500, lambda src, p: got.append(p))
        z = np.zeros(1 << 19)
        for rep in range(3):
            got.clear()
            e0.send_am(1, 500, {"arr": z})
            _drain_until(e1, lambda: got, timeout=60)
            np.testing.assert_array_equal(got[0]["arr"], z)
        assert e0.wire_stats["frames_compressed"] == 0, e0.wire_stats
    finally:
        e0.fini()
        e1.fini()


def test_codec_negotiation():
    assert wire.negotiate_codec(["zlib"], ["zlib"]) == "zlib"
    assert wire.negotiate_codec(["zlib"], []) is None
    assert wire.negotiate_codec([], ["zlib"]) is None
    assert wire.negotiate_codec(["zlib", "lz4"],
                                ["lz4", "zlib"]) in ("lz4", "zlib")


# ---------------------------------------------------------------------- #
# codec table round-trips — parameterized over EVERY registered codec    #
# (incl. lz4 when the module is present: its registration branch is no   #
# longer uncovered), lossless exactly, quantized within tolerance        #
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("codec", sorted(wire.CODECS))
def test_codec_roundtrip(codec):
    ent = wire.CODECS[codec]
    if ent.lossless:
        body = b"an eminently compressible control payload " * 199
        pieces = wire.compress_body(body, codec)
        assert pieces is not None, f"{codec} did not shrink zeros"
        assert bytes(pieces[0])[0] == wire.K_COMP
        out = wire.decompress_body(memoryview(b"".join(
            bytes(p) for p in pieces)))
        assert out == body                      # lossless: exact bytes
    else:
        arr = (np.random.RandomState(3).randn(4097) * 5).astype(np.float64)
        enc = wire.quantize_buffer(
            memoryview(np.ascontiguousarray(arr)).cast("B"), "d", codec)
        assert len(enc) < arr.nbytes // 2       # really smaller
        raw = wire.dequantize_buffer(enc)
        assert len(raw) == arr.nbytes           # exact layout back
        out = np.frombuffer(raw, np.float64)
        rel = np.abs(out - arr).max() / np.abs(arr).max()
        assert rel < 0.01, rel                  # lossy within tolerance


def test_lz4_advertised_only_when_installed():
    assert ("lz4" in wire.available_codecs()) == \
        (wire._lz4_mod() is not None)


def test_quant_codec_never_compresses_frame_bodies():
    with pytest.raises(ValueError):
        wire.compress_body(b"x" * 2048, "qint8")
    assert wire.available_quant_codecs() == ["qbf16", "qint8"]
    assert all(c not in wire.available_codecs()
               for c in wire.available_quant_codecs())


def test_quant_codec_negotiation():
    assert wire.normalize_quant_codec("") is None
    assert wire.normalize_quant_codec("bf16") == "qbf16"
    assert wire.normalize_quant_codec("qint8") == "qint8"
    with pytest.raises(ValueError):
        wire.normalize_quant_codec("zlib")   # lossless: wrong family
    with pytest.raises(ValueError):
        wire.normalize_quant_codec("int4")   # unknown
    assert wire.negotiate_quant_codec("qint8", ["qbf16", "qint8"]) \
        == "qint8"
    assert wire.negotiate_quant_codec("qint8", []) is None
    assert wire.negotiate_quant_codec("qint8", ["qbf16"]) is None
    assert wire.negotiate_quant_codec(None, ["qint8"]) is None


def test_quantized_bufspec_roundtrip_through_rx_xfer():
    """A transfer header announcing a BUF_QUANT buffer reassembles and
    DECODES transparently: the unpickled array has the original
    dtype/shape with quantized values."""
    arr = np.random.RandomState(9).rand(1 << 12)          # 32 KB f64
    bufs = []
    fr = pickle.dumps((0, 7, {"arr": arr}), protocol=5,
                      buffer_callback=bufs.append)
    v = bufs[0].raw()
    enc = memoryview(wire.quantize_buffer(v, "d", "qint8"))
    hdr = wire.pack_xfer_hdr(
        11, fr, [(wire.BUF_CHUNKED | wire.BUF_QUANT, enc.nbytes, None)])
    xid, frame, specs = wire.parse_xfer_hdr(
        memoryview(hdr).toreadonly())
    assert xid == 11 and specs[0][0] == (wire.BUF_CHUNKED
                                         | wire.BUF_QUANT)
    rx = wire.RxXfer(frame, specs)
    done = rx.feed(0, 0, enc)
    assert done
    src, tag, payload = rx.message()
    out = np.asarray(payload["arr"])
    assert out.dtype == arr.dtype and out.shape == arr.shape
    np.testing.assert_array_equal(out, wire.qdq_array(arr, "qint8"))


def test_quantized_transfer_over_tcp_and_eligibility():
    """End to end over real sockets: an ``_qz_ok``-marked bulk float
    message delivers EXACTLY the qdq values (deterministic codec), an
    unmarked one stays bit-exact lossless, and the per-link labeled
    ratio gauge moves above 1."""
    e0, e1 = _engines(2, chunk_bytes=1 << 14, quantize="int8")
    try:
        peer = e0._peer_to(1)
        deadline = time.time() + 10
        while time.time() < deadline:
            with peer.cond:
                if peer.qz_codec:
                    break
            time.sleep(0.005)
        with peer.cond:
            assert peer.qz_codec == "qint8"
        got = []
        e1.tag_register(700, lambda src, p: got.append(p))
        arr = np.random.RandomState(11).rand(1 << 15)     # 256 KB
        e0.send_am(1, 700, {"arr": arr, "_qz_ok": True})
        _drain_until(e1, lambda: got, timeout=30)
        out = np.asarray(got[0]["arr"])
        np.testing.assert_array_equal(out, wire.qdq_array(arr, "qint8"))
        assert e0.wire_stats["bufs_quantized"] == 1
        assert e0.codec_ratio(1, "qint8") > 1.0
        assert e0.quantize_ratio() > 1.0
        # eligibility: the UNMARKED twin of the same payload is exact
        got.clear()
        e0.send_am(1, 700, {"arr": arr})
        _drain_until(e1, lambda: got, timeout=30)
        np.testing.assert_array_equal(np.asarray(got[0]["arr"]), arr)
        assert e0.wire_stats["bufs_quantized"] == 1   # unchanged
        # non-float bulk stays lossless even when marked
        got.clear()
        ints = np.arange(1 << 15, dtype=np.int64)
        e0.send_am(1, 700, {"arr": ints, "_qz_ok": True})
        _drain_until(e1, lambda: got, timeout=30)
        np.testing.assert_array_equal(np.asarray(got[0]["arr"]), ints)
        assert e0.wire_stats["bufs_quantized"] == 1   # still unchanged
    finally:
        e0.fini()
        e1.fini()


def test_quantize_default_knobs_keep_wire_lossless():
    """Off-by-default safety (the acceptance differential): at default
    knobs an ``_qz_ok``-marked bulk message still travels lossless —
    nothing advertises "qz", nothing negotiates, nothing encodes."""
    e0, e1 = _engines(2, chunk_bytes=1 << 14)
    try:
        assert e0._quantize is None
        got = []
        e1.tag_register(800, lambda src, p: got.append(p))
        arr = np.random.RandomState(13).rand(1 << 15)
        e0.send_am(1, 800, {"arr": arr, "_qz_ok": True})
        _drain_until(e1, lambda: got, timeout=30)
        np.testing.assert_array_equal(np.asarray(got[0]["arr"]), arr)
        assert e0.wire_stats["bufs_quantized"] == 0
        assert e0.codec_ratio(1, "qint8") == 1.0
    finally:
        e0.fini()
        e1.fini()


def test_default_knobs_keep_compression_off():
    """Off-by-default safety: at default knobs nothing ever compresses
    and the wire carries plain frames on a fast link."""
    e0, e1 = _engines(2)
    try:
        assert e0.compress_threshold_mbps == 0
        got = []
        e1.tag_register(600, lambda src, p: got.append(p))
        e0.send_am(1, 600, {"arr": np.zeros(1 << 18)})
        _drain_until(e1, lambda: got)
        assert e0.wire_stats["frames_compressed"] == 0
    finally:
        e0.fini()
        e1.fini()


# ---------------------------------------------------------------------- #
# coalescing: frames per message, counted                                #
# ---------------------------------------------------------------------- #
def _burst_small_ams(coalesce_max_bytes, n_msgs=3000):
    """Burst ``n_msgs`` tiny AMs from rank 0 over a loopback pair and
    drain them on rank 1; (payload order as received, rank 0's frame /
    message / coalesced-message counts for the burst)."""
    e0, e1 = _engines(2, coalesce_max_bytes=coalesce_max_bytes)
    try:
        got = []
        e1.tag_register(100, lambda src, p: got.append(p["i"]))
        keys = ("batches", "msgs_sent", "coalesced_msgs")
        before = {k: e0.wire_stats[k] for k in keys}
        for i in range(n_msgs):
            e0.send_am(1, 100, {"i": i})
        _drain_until(e1, lambda: len(got) >= n_msgs, timeout=60)
        # the writer books a frame after the send that delivered it
        deadline = time.time() + 10
        while e0.wire_stats["msgs_sent"] - before["msgs_sent"] < n_msgs \
                and time.time() < deadline:
            time.sleep(0.001)
        return got, {k: e0.wire_stats[k] - before[k] for k in keys}
    finally:
        e0.fini()
        e1.fini()


def test_coalescing_halves_frames_for_small_am_burst():
    """What coalescing buys is fewer frames (one syscall each) for the
    same messages: with it off every message is its own frame; with the
    default 64 KB budget the same burst rides at most half as many, and
    every message still arrives once, in order."""
    n = 3000
    got_off, off = _burst_small_ams(0, n)
    got_on, on = _burst_small_ams(1 << 16, n)
    assert got_off == got_on == list(range(n))
    assert off["msgs_sent"] == on["msgs_sent"] == n
    assert off["batches"] == n and off["coalesced_msgs"] == 0, off
    assert on["batches"] * 2 <= off["batches"], (on, off)
    assert on["coalesced_msgs"] > n // 2, on


# ---------------------------------------------------------------------- #
# GET aggregation                                                        #
# ---------------------------------------------------------------------- #
def test_gets_issued_in_one_progress_cycle_batch_per_peer():
    """Three GETs triggered by one delivered message ride ONE request
    frame and ONE reply frame (msg_count proves it), and every callback
    still fires with its own data."""
    from parsec_tpu.comm.local import LocalFabric

    fab = LocalFabric(2)
    e0, e1 = fab.engine(0), fab.engine(1)
    handles = [e0.mem_register(np.full((4,), i, np.float64))
               for i in range(3)]
    got = {}

    def trigger(src, payload):
        for i, h in enumerate(handles):
            e1.get(0, h.handle_id,
                   lambda arr, i=i: got.__setitem__(i, arr))

    e1.tag_register(700, trigger)
    e0.send_am(1, 700, {"go": 1})
    e1.progress()           # delivers trigger; flush batches the 3 GETs
    before = fab.msg_count  # trigger + 1 batched GET request
    assert before == 2, before
    e0.progress()           # serves all three in one reply
    assert fab.msg_count == 3
    e1.progress()           # callbacks fire
    assert set(got) == {0, 1, 2}
    for i in range(3):
        np.testing.assert_array_equal(got[i], np.full((4,), i))


def test_get_outside_progress_sends_immediately():
    from parsec_tpu.comm.local import LocalFabric

    fab = LocalFabric(2)
    e0, e1 = fab.engine(0), fab.engine(1)
    h = e0.mem_register(np.arange(6, dtype=np.float64))
    got = []
    e1.get(0, h.handle_id, got.append)
    assert fab.msg_count == 1       # the request left right away
    e0.progress()
    e1.progress()
    assert got and np.array_equal(got[0], np.arange(6))


# ---------------------------------------------------------------------- #
# adaptive eager/rendezvous cutoff                                       #
# ---------------------------------------------------------------------- #
def _remote_dep_pair(adaptive):
    from parsec_tpu.comm.local import LocalFabric
    from parsec_tpu.comm.remote_dep import RemoteDepEngine

    if adaptive:
        params.set_cmdline("comm_adaptive_short_limit", "1")
    try:
        fab = LocalFabric(2)
        eng = RemoteDepEngine(fab.engine(0))
    finally:
        if adaptive:
            params.unset_cmdline("comm_adaptive_short_limit")
    return eng


def test_adaptive_short_limit_tracks_bandwidth_delay_product():
    eng = _remote_dep_pair(adaptive=True)
    static = eng.short_limit
    # no measurements yet: static cutoff
    assert eng.short_limit_for(1) == static
    # 50 MB/s link, 10 ms GET round-trip -> BDP 500 KB
    eng.ce.link_bw_mbps = lambda peer: 50.0
    eng._note_get_rtt(1, 0.010)
    assert eng.short_limit_for(1) == 500_000
    assert eng.adaptive_limits[1] == 500_000
    # the static knob is the floor...
    eng._note_get_rtt(1, 0.010)
    eng.ce.link_bw_mbps = lambda peer: 0.001   # 1 KB/s: BDP ~10 bytes
    assert eng.short_limit_for(1) == static
    # ...and comm_short_limit_max the ceiling
    eng.ce.link_bw_mbps = lambda peer: 1e6     # absurd link
    assert eng.short_limit_for(1) == eng._short_limit_max


def test_adaptive_off_by_default_keeps_static_cutoff():
    eng = _remote_dep_pair(adaptive=False)
    eng.ce.link_bw_mbps = lambda peer: 50.0
    eng._note_get_rtt(1, 0.010)
    assert eng.short_limit_for(1) == eng.short_limit


def test_get_rtt_ewma_feeds_from_rendezvous():
    """A real rendezvous through _timed_get populates the per-peer RTT
    EWMA the adaptive cutoff reads."""
    from parsec_tpu.comm.local import LocalFabric
    from parsec_tpu.comm.remote_dep import RemoteDepEngine

    fab = LocalFabric(2)
    r0 = RemoteDepEngine(fab.engine(0))
    r1 = RemoteDepEngine(fab.engine(1))
    h = r0.ce.mem_register(np.ones((8,), np.float64))
    got = []
    r1._timed_get(0, h.handle_id, got.append)
    r0.ce.progress()
    r1.ce.progress()
    assert got and 0 in r1._get_rtt
    assert r1._get_rtt[0] > 0


# ---------------------------------------------------------------------- #
# lane-schedule uniformity (wave_dist satellite)                         #
# ---------------------------------------------------------------------- #
def test_lane_schedule_uniformity_matching_digests_pass():
    from parsec_tpu.comm.local import LocalFabric
    from parsec_tpu.dsl.ptg.wave_dist import check_lane_schedule_uniformity
    from parsec_tpu.utils.spmd import spmd_threads

    def rank_fn(r, fab):
        check_lane_schedule_uniformity(fab.engine(r), "same", timeout=20)
        return "ok"

    results, _f = spmd_threads(2, rank_fn, timeout=60)
    assert results == ["ok", "ok"]


def test_lane_schedule_uniformity_mismatch_fails_fast():
    from parsec_tpu.comm.local import LocalFabric
    from parsec_tpu.dsl.ptg.wave import WaveError
    from parsec_tpu.dsl.ptg.wave_dist import check_lane_schedule_uniformity
    from parsec_tpu.utils.spmd import spmd_threads

    def rank_fn(r, fab):
        try:
            check_lane_schedule_uniformity(
                fab.engine(r), f"digest-{r}", timeout=20)
            return "no-error"
        except WaveError as exc:
            return f"raised: {exc}"

    results, _f = spmd_threads(2, rank_fn, timeout=60)
    assert all(r.startswith("raised") for r in results), results
    assert "diverge" in results[0]


# ---------------------------------------------------------------------- #
# pool-tile-spec ownership guard (wave_dist satellite)                   #
# ---------------------------------------------------------------------- #
def test_pool_tile_spec_requires_contract_or_owned_tile():
    """A rank owning no tile of a pool whose collection lacks the
    static tile_shape/dtype contract gets a clear error, not a remote
    fetch or an opaque failure."""
    import types
    from parsec_tpu.dsl.ptg.wave import WaveError
    from parsec_tpu.dsl.ptg.wave_dist import DistWaveRunner

    class NoContractColl:
        dtype = None

        def rank_of(self, m, n):
            return 1          # every tile owned elsewhere

        def data_of(self, m, n):  # pragma: no cover - must not be hit
            raise AssertionError("data_of reached for unowned tile")

    shim = types.SimpleNamespace(
        rank=0, _n_real_colls=1, pool_names=["descA"],
        collections={"descA": NoContractColl()},
        _pool_shapes=[None], _pool_coords=[[(0, 0), (1, 0)]],
        _scratch={})
    with pytest.raises(WaveError, match="static"):
        DistWaveRunner._pool_tile_spec(shim, 0)


def test_pool_tile_spec_uses_locally_owned_coord():
    import types
    from parsec_tpu.dsl.ptg.wave_dist import DistWaveRunner

    probed = []

    class HalfOwnedColl:
        dtype = None

        def rank_of(self, m, n):
            return 0 if (m, n) == (1, 0) else 1

        def data_of(self, m, n):
            probed.append((m, n))
            payload = np.zeros((4, 4), np.float32)
            host = types.SimpleNamespace(payload=payload)
            return types.SimpleNamespace(sync_to_host=lambda: host)

    shim = types.SimpleNamespace(
        rank=0, _n_real_colls=1, pool_names=["descA"],
        collections={"descA": HalfOwnedColl()},
        _pool_shapes=[None], _pool_coords=[[(0, 0), (1, 0)]],
        _scratch={})
    sh, dt = DistWaveRunner._pool_tile_spec(shim, 0)
    assert sh == (4, 4) and dt == np.float32
    assert probed == [(1, 0)]       # the owned coord, not coords[0]
