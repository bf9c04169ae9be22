"""Reliable TCP sessions (ISSUE 10): reconnect + seq-numbered replay.

A transient link fault (flap, idle-timeout RST, NAT drop) must not
masquerade as rank death: with ``comm_reconnect_timeout`` set the torn
peer goes SUSPECT, a reconnector re-establishes the link, the sender
replays the unacked gap and the receiver dedups by seq — exactly-once
delivery across the fault, bit-identical to a failure-free run. Only
budget exhaustion (or a protocol violation) escalates to the
``RankFailedError`` fail-fast/elastic path, and a mixed-version peer
(no ``"rs"`` capability) keeps today's fail-fast bit for bit.
"""
import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from parsec_tpu.comm.tcp import RankFailedError, TCPCommEngine, free_ports
from parsec_tpu.comm import wire
from parsec_tpu.ft.inject import FaultInjector

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TAG = 100


def _engines(n, **kw):
    ports = free_ports(n)
    eps = [("127.0.0.1", p) for p in ports]
    import concurrent.futures as cf
    with cf.ThreadPoolExecutor(n) as ex:
        return list(ex.map(lambda r: TCPCommEngine(r, eps, **kw), range(n)))


def _wait(pred, timeout=10.0, step=0.005):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(step)
    return pred()


def _peer_obj(e, r):
    with e._conn_cond:
        return e._peers.get(r)


def _wait_session(e0, e1, timeout=10.0):
    """Both directions negotiated the "rs" capability."""
    ok = _wait(lambda: (_peer_obj(e0, e1.rank) is not None
                        and _peer_obj(e0, e1.rank).rs_ok
                        and _peer_obj(e1, e0.rank) is not None
                        and _peer_obj(e1, e0.rank).rs_ok), timeout)
    assert ok, "session capability never negotiated"


def test_session_flap_delivers_exactly_once():
    """A hard socket close mid-stream is absorbed: the peers reconnect
    (RECONNECTS >= 1), every message before and after the flap arrives
    exactly once and in order, and nobody is declared dead."""
    e0, e1 = _engines(2, reconnect_timeout=10.0)
    got = []
    e1.tag_register(TAG, lambda src, p: got.append(p["i"]))
    try:
        _wait_session(e0, e1)
        for i in range(5):
            e0.send_am(1, TAG, {"i": i})
        assert _wait(lambda: (e1.progress(), len(got) >= 5)[1])
        # flap: hard-close the established socket (both ends see it)
        _peer_obj(e0, 1).sock.shutdown(socket.SHUT_RDWR)
        assert _wait(lambda: e0.wire_stats["reconnects"] >= 1
                     and e1.wire_stats["reconnects"] >= 1)
        for i in range(5, 8):
            e0.send_am(1, TAG, {"i": i})
        assert _wait(lambda: (e1.progress(), len(got) >= 8)[1])
        assert got == list(range(8))   # exactly once, in order
        assert not e0.dead_peers and not e1.dead_peers
        assert not e0.peer_suspect(1) and not e1.peer_suspect(0)
        assert e0.suspect_ms() > 0   # the episode was accounted
    finally:
        e0.fini()
        e1.fini()


def test_replay_after_flap_bit_identical():
    """Frames lost in flight (sent into a peer whose kernel already
    tore the connection) are REPLAYED from the window after the
    reconnect: the receiver observes the exact same payload sequence,
    bit for bit, as a failure-free run."""
    e0, e1 = _engines(2, reconnect_timeout=10.0)
    got = []
    e1.tag_register(TAG, lambda src, p: got.append(np.array(p["arr"])))
    rng = np.random.RandomState(7)
    sent = [rng.rand(64).astype(np.float64) for _ in range(12)]
    try:
        _wait_session(e0, e1)
        # tear the RECEIVER side first: the sender's next writes land
        # in a dead connection (accepted-but-lost) and must replay
        _peer_obj(e1, 0).sock.shutdown(socket.SHUT_RDWR)
        for a in sent:
            e0.send_am(1, TAG, {"arr": a})
        assert _wait(lambda: (e1.progress(), len(got) >= 12)[1], 15.0)
        assert len(got) == 12
        for a, b in zip(sent, got):
            np.testing.assert_array_equal(a, b)   # bit-identical
        assert e0.wire_stats["reconnects"] >= 1
        assert not e0.dead_peers and not e1.dead_peers
    finally:
        e0.fini()
        e1.fini()


def test_injected_dup_delivers_am_exactly_once():
    """``ft_inject dup`` on a session link duplicates the FRAME (same
    seq) at the wire: the receiver's dedup keeps the active message
    exactly-once and counts the duplicate."""
    e0, e1 = _engines(2, reconnect_timeout=10.0)
    got = []
    e1.tag_register(TAG, lambda src, p: got.append(p["i"]))
    try:
        _wait_session(e0, e1)
        e0._ft = FaultInjector.from_spec("dup:rank=0:nth=2", rank=0)
        for i in range(4):
            e0.send_am(1, TAG, {"i": i})
        assert _wait(lambda: (e1.progress(), len(got) >= 4)[1])
        assert got == [0, 1, 2, 3]   # the duplicated AM ran ONCE
        assert _wait(lambda: e1.wire_stats["dup_dropped"] >= 1)
        assert e0._ft.stats["duplicated"] == 1
    finally:
        e0.fini()
        e1.fini()


def test_mixed_version_peer_keeps_fail_fast():
    """One end without the knob never advertises "rs": a torn socket
    is rank death on the spot, exactly the pre-session contract."""
    e0, e1 = _engines(2, reconnect_timeout=0.0)
    # e0 re-creates nothing: BOTH engines came up session-less; flip
    # e0's local enable to prove the gate is the NEGOTIATION, not the
    # local knob alone
    try:
        assert _wait(lambda: _peer_obj(e0, 1) is not None
                     and _peer_obj(e0, 1).hello_seen)
        assert not _peer_obj(e0, 1).rs_ok
        _peer_obj(e1, 0).sock.shutdown(socket.SHUT_RDWR)
        assert _wait(lambda: 1 in e0.dead_peers or 0 in e1.dead_peers)
        assert e0.wire_stats["reconnects"] == 0
        assert e1.wire_stats["reconnects"] == 0
        assert not e0.peer_suspect(1) and not e1.peer_suspect(0)
        dead_side = e0 if 1 in e0.dead_peers else e1
        with pytest.raises(RankFailedError):
            dead_side.send_am(1 - dead_side.rank, TAG, {"x": 1})
    finally:
        e0._closing = True
        e1._closing = True
        e0.fini()
        e1.fini()


def test_budget_exhaustion_escalates_to_rank_failed():
    """A link that never comes back exhausts ``comm_reconnect_timeout``
    and escalates through the SAME failure funnel a torn session-less
    socket takes: dead_peers + on_peer_failure + RankFailedError."""
    e0, e1 = _engines(2, reconnect_timeout=0.6, reconnect_backoff=0.05)
    failures = []
    e1.on_peer_failure = lambda peer, reason: failures.append((peer, reason))
    try:
        _wait_session(e0, e1)
        # a PERMANENT link fault: the disconnect directive hard-closes
        # the socket and rejects every reconnect (dial-out and
        # accepted resume alike) forever
        e0._ft = FaultInjector.from_spec("disconnect:rank=0:nth=1", rank=0)
        t0 = time.time()
        e0.send_am(1, TAG, {"x": 0})   # triggers the disconnect
        assert _wait(lambda: 0 in e1.dead_peers and 1 in e0.dead_peers,
                     15.0)
        assert time.time() - t0 < 12.0
        assert failures and failures[0][0] == 0
        assert "budget exhausted" in failures[0][1]
        with pytest.raises(RankFailedError):
            e1.send_am(0, TAG, {"x": 1})
        assert not e1.peer_suspect(0) and not e0.peer_suspect(1)
        assert e0.wire_stats["reconnects"] == 0
        assert e1.wire_stats["reconnects"] == 0
    finally:
        e0._closing = True
        e1._closing = True
        e1.fini()
        e0.fini()


def test_detector_defers_during_in_budget_flap():
    """With heartbeats ON and a flap LONGER than the heartbeat timeout
    but inside the reconnect budget, the detector must NOT evict: the
    session layer owns the verdict while the link is torn, and the
    resume resets the silence baseline."""
    from parsec_tpu.ft.detector import HeartbeatDetector
    e0, e1 = _engines(2, reconnect_timeout=10.0)
    det = HeartbeatDetector(e0, interval=0.05, timeout=0.3).start()
    got = []
    e1.tag_register(TAG, lambda src, p: got.append(p["i"]))
    try:
        _wait_session(e0, e1)
        assert _wait(lambda: det.is_established(1), 10.0)
        # flap with the link held DOWN for 0.6 s (> 2x the hb timeout):
        # the injector rejects reconnects until the duration elapses
        e0._ft = FaultInjector.from_spec(
            "flap:rank=0:nth=1:duration=0.6", rank=0)
        e0.send_am(1, TAG, {"i": 0})
        assert _wait(lambda: e0.peer_suspect(1), 5.0)
        time.sleep(0.8)   # well past the heartbeat deadline
        assert det.evictions == 0
        assert 1 not in e0.dead_peers
        assert _wait(lambda: e0.wire_stats["reconnects"] >= 1, 10.0)
        e0.send_am(1, TAG, {"i": 1})
        assert _wait(lambda: (e1.progress(), len(got) >= 2)[1])
        assert got == [0, 1]   # the flapped frame itself was not lost
        time.sleep(0.5)        # a few detector ticks after the resume
        assert det.evictions == 0 and 1 not in e0.dead_peers
    finally:
        det.stop()
        e0.fini()
        e1.fini()


def test_chunked_transfer_survives_flap():
    """A flap in the middle of a stream of chunked (multi-frame) bulk
    messages: half-landed transfers stay parked on the peer, the
    replayed chunks complete them, and every payload arrives intact."""
    e0, e1 = _engines(2, reconnect_timeout=10.0, chunk_bytes=1 << 12)
    got = []
    e1.tag_register(TAG, lambda src, p: got.append(
        (p["i"], np.array(p["arr"]))))
    rng = np.random.RandomState(3)
    payloads = [rng.rand(8192).astype(np.float64) for _ in range(16)]
    try:
        _wait_session(e0, e1)

        def sender():
            for i, a in enumerate(payloads):
                e0.send_am(1, TAG, {"i": i, "arr": a})

        t = threading.Thread(target=sender, daemon=True)
        t.start()
        time.sleep(0.002)   # land the tear somewhere inside the stream
        _peer_obj(e1, 0).sock.shutdown(socket.SHUT_RDWR)
        t.join(10)
        assert not t.is_alive()
        assert _wait(lambda: (e1.progress(), len(got) >= 16)[1], 20.0)
        assert [i for i, _ in got] == list(range(16))
        for i, arr in got:
            np.testing.assert_array_equal(arr, payloads[i])
        # on a fast host the tear can land after the last frame left
        # e0: it then learns of the flap from e1's redial, a beat
        # after the payloads (already delivered) were counted
        assert _wait(lambda: (e0.progress(), e1.progress(),
                              e0.wire_stats["reconnects"] >= 1)[2])
        assert not e0.dead_peers and not e1.dead_peers
    finally:
        e0.fini()
        e1.fini()


def test_quantized_transfer_replays_bit_identical_after_flap():
    """Session-layer x quantized-codec interplay (ISSUE 14): the lossy
    encoding happens at ENQUEUE, before the K_SEQ envelope, so the
    replay window retains the ENCODED bytes — a flap mid-stream
    replays them and the receiver observes byte-for-byte the same
    quantized values a failure-free quantized run delivers (asserted
    against wire.qdq_array, which IS that value by construction)."""
    e0, e1 = _engines(2, reconnect_timeout=10.0, chunk_bytes=1 << 12,
                      quantize="int8")
    got = []
    e1.tag_register(TAG, lambda src, p: got.append(
        (p["i"], np.array(p["arr"]))))
    rng = np.random.RandomState(21)
    payloads = [rng.rand(8192).astype(np.float64) for _ in range(16)]
    try:
        _wait_session(e0, e1)
        p0 = _peer_obj(e0, 1)
        assert _wait(lambda: (lambda: p0.qz_codec == "qint8")()), \
            "quantized codec never negotiated"

        def sender():
            for i, a in enumerate(payloads):
                e0.send_am(1, TAG, {"i": i, "arr": a, "_qz_ok": True})

        t = threading.Thread(target=sender, daemon=True)
        t.start()
        time.sleep(0.002)   # land the tear somewhere inside the stream
        _peer_obj(e1, 0).sock.shutdown(socket.SHUT_RDWR)
        t.join(10)
        assert not t.is_alive()
        assert _wait(lambda: (e1.progress(), len(got) >= 16)[1], 20.0)
        assert [i for i, _ in got] == list(range(16))
        for i, arr in got:
            np.testing.assert_array_equal(
                arr, wire.qdq_array(payloads[i], "qint8"))
        # on a fast host the tear can land after the last frame left
        # e0: it then learns of the flap from e1's redial, a beat
        # after the payloads (already delivered) were counted
        assert _wait(lambda: (e0.progress(), e1.progress(),
                              e0.wire_stats["reconnects"] >= 1)[2])
        assert e0.wire_stats["bufs_quantized"] == 16
        assert not e0.dead_peers and not e1.dead_peers
    finally:
        e0.fini()
        e1.fini()


def test_quantize_mixed_version_peer_negotiates_down_to_lossless():
    """A peer whose HELLO carries no "qz" capability (mixed version /
    knob unset on its side) must NEVER receive quantized buffers —
    the link silently stays lossless, bit for bit."""
    # e0 wants int8; e1 runs with the knob unset and advertises no "qz"
    ports = free_ports(2)
    eps = [("127.0.0.1", p) for p in ports]
    import concurrent.futures as cf
    with cf.ThreadPoolExecutor(2) as ex:
        e0, e1 = list(ex.map(
            lambda r: TCPCommEngine(
                r, eps, reconnect_timeout=10.0, chunk_bytes=1 << 12,
                quantize="int8" if r == 0 else ""),
            range(2)))
    got = []
    e1.tag_register(TAG, lambda src, p: got.append(np.array(p["arr"])))
    try:
        _wait_session(e0, e1)
        p = _peer_obj(e0, 1)
        with p.cond:
            assert p.qz_codec is None   # negotiated down
        arr = np.random.RandomState(23).rand(8192)
        e0.send_am(1, TAG, {"arr": arr, "_qz_ok": True})
        assert _wait(lambda: (e1.progress(), got)[1], 15.0)
        np.testing.assert_array_equal(got[0], arr)   # bit-exact
        assert e0.wire_stats["bufs_quantized"] == 0
    finally:
        e0.fini()
        e1.fini()


def test_partial_frame_resume_claim():
    """The receiver's byte-level resume claim (satellite: `_recv_exact`
    truncation offset feeds the session instead of being discarded):
    only a partial that provably is the NEXT expected data frame may
    resume mid-body; anything else falls back to whole-frame replay."""
    e0, e1 = _engines(2, reconnect_timeout=5.0)
    try:
        _wait_session(e0, e1)
        p = _peer_obj(e0, 1)
        body = wire.pack_seq(0, 7) + b"x" * 32
        with p.cond:
            p.rs_rx_seq = 6
            # next expected frame (seq 7), truncated at 20 of 41 bytes
            p.rs_rx_partial = (len(body), bytearray(body[:20]))
            claim = e0._partial_claim_locked(p)
        assert claim == {"seq": 7, "off": 20}
        with p.cond:
            # NOT the next expected frame: claim refused and discarded
            p.rs_rx_seq = 7
            p.rs_rx_partial = (len(body), bytearray(body[:20]))
            assert e0._partial_claim_locked(p) is None
            assert p.rs_rx_partial is None
            # truncated inside the 9-byte K_SEQ header: no claim
            p.rs_rx_seq = 6
            p.rs_rx_partial = (len(body), bytearray(body[:4]))
            assert e0._partial_claim_locked(p) is None
    finally:
        e0.fini()
        e1.fini()


def _run_wave_ranks(nb_ranks, env_extra, timeout=240):
    ports = free_ports(nb_ranks)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "tests", "tcp_rank_main.py"),
         str(r), str(nb_ranks), ",".join(map(str, ports)), "0", "wave"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
        for r in range(nb_ranks)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, (p.returncode, out, err)
        outs.append(json.loads(out.strip().splitlines()[-1]))
    return outs


def test_dpotrf_2rank_flap_matches_failure_free():
    """Acceptance leg: a 2-rank distributed-wave dpotrf over real OS
    processes with a chaos-injected link flap completes with ZERO rank
    evictions and numerics BIT-IDENTICAL to the failure-free run (the
    replay path re-delivers the exact bytes, so the factor cannot
    drift)."""
    clean = _run_wave_ranks(2, {})
    flapped = _run_wave_ranks(2, {
        "PARSEC_MCA_comm_reconnect_timeout": "20",
        "PARSEC_MCA_ft_inject": "flap:rank=0:nth=2:duration=0.05",
    })
    assert sum(o["wire"]["reconnects"] for o in flapped) >= 1, flapped
    for c, f in zip(clean, flapped):
        assert f["max_err"] == c["max_err"]   # bit-identical factor
    assert all(o["wire"]["reconnects"] == 0 for o in clean)


def test_redistribution_survives_flap_bit_identical():
    """ISSUE 19 chaos leg: a ``flap:rank=*`` landing in the MIDDLE of
    a planned collective redistribution (xfer/plan.py rounds over the
    session wire) is absorbed by reconnect + replay — the reshard
    completes bit-identical to the source, the exchanged plan digests
    agree, and nobody is declared dead."""
    from parsec_tpu.collections import TwoDimBlockCyclic
    from parsec_tpu.xfer import run_redistribution
    nb = 2
    lm = ln = 32
    src_np = np.random.RandomState(11).rand(lm, ln)
    engines = _engines(nb, reconnect_timeout=10.0)
    e0, e1 = engines
    try:
        _wait_session(e0, e1)
        # rank 0's 2nd post-install send is its round-1 bulk transfer:
        # the link tears with the frame unflushed — replay must carry it
        e0._ft = FaultInjector.from_spec(
            "flap:rank=*:nth=2:duration=0.05", rank=0)
        outs = [None] * nb
        errs = []

        def run(r):
            try:
                src = TwoDimBlockCyclic(
                    lm, ln, 4, 4, P=nb, Q=1, nodes=nb, rank=r,
                    dtype=np.float64).from_numpy(src_np)
                tgt = TwoDimBlockCyclic(
                    lm, ln, 4, 4, P=1, Q=nb, nodes=nb, rank=r,
                    dtype=np.float64).from_numpy(np.zeros((lm, ln)))
                tp = run_redistribution(src, tgt, engines[r],
                                        timeout=30.0)
                outs[r] = (tp.plan_digest,
                           {c: np.array(tgt.tile(*c))
                            for c in tgt.local_tiles()})
            except BaseException as exc:
                errs.append(exc)

        ts = [threading.Thread(target=run, args=(r,)) for r in range(nb)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts), "redistribution hung"
        assert not errs, errs
        got = np.zeros((lm, ln))
        for r in range(nb):
            for (m, n), arr in outs[r][1].items():
                got[m * 4:m * 4 + arr.shape[0],
                    n * 4:n * 4 + arr.shape[1]] = arr
        np.testing.assert_array_equal(got, src_np)   # bit-identical
        assert outs[0][0] == outs[1][0]              # digests agree
        assert e0._ft.stats["flaps"] >= 1            # the fault fired
        assert e0.wire_stats["reconnects"] >= 1
        assert not e0.dead_peers and not e1.dead_peers
    finally:
        for e in engines:
            e.fini()
