"""serve/ (ISSUE 18): multi-tenant persistent serving.

Covers the wire envelopes, local admission + submission lifecycle on a
persistent context, the remote ServeClient <-> SessionServer path over
an in-process AM fabric, tenant-stamped flow contexts feeding the
cross-rank tooling, per-tenant live-health attribution, and the
knob-unset inertness contract (no server constructed = nothing changes).
"""
import threading
import time

import pytest

import parsec_tpu
from parsec_tpu import dtd
from parsec_tpu.comm import LocalFabric, wire
from parsec_tpu.comm.engine import FlowIds, TAG_ACTIVATE
from parsec_tpu.dsl.dtd import VALUE
from parsec_tpu.obs import (CommObs, MetricsRegistry, analyze,
                            load_flow_events, merge_trace_docs,
                            stitch_flows)
from parsec_tpu.obs.live import LiveHealth, fleet_health, format_health
from parsec_tpu.obs.spans import (SERVE_INFLIGHT_PREFIX,
                                  SERVE_P99_LATENCY_PREFIX,
                                  SERVE_QUOTA_BYTES_PREFIX, SERVE_TENANTS)
from parsec_tpu.profiling.trace import Profile
from parsec_tpu.serve import (AdmissionError, ServeClient, SessionServer)
from parsec_tpu.utils.params import params


# ---------------------------------------------------------------------- #
# wire envelopes                                                         #
# ---------------------------------------------------------------------- #
def test_serve_envelope_roundtrip():
    req = wire.serve_request("submit", 7, tenant="acme", ntasks=3)
    assert wire.parse_serve(req) is req
    assert req["op"] == "submit" and req["req"] == 7
    assert req["tenant"] == "acme" and req["ntasks"] == 3
    rep = wire.serve_reply(7, True, ticket=12)
    assert wire.parse_serve(rep)["ok"] is True
    assert rep["ticket"] == 12 and rep["sv"] == wire.SERVE_PROTO_VERSION


def test_serve_envelope_rejects_malformed():
    with pytest.raises(ValueError):
        wire.parse_serve(b"not a dict")
    with pytest.raises(ValueError):
        wire.parse_serve({"op": "open", "req": 1})        # no version
    with pytest.raises(ValueError):
        wire.parse_serve({"sv": wire.SERVE_PROTO_VERSION + 1, "req": 1})
    with pytest.raises(ValueError):
        wire.parse_serve({"sv": 1, "op": "open"})         # no req id


# ---------------------------------------------------------------------- #
# local lifecycle on one persistent context                              #
# ---------------------------------------------------------------------- #
def _count_build(ctx, counter, n_tasks=4):
    """A DTD closure submission: build returns a sealed, not-yet-added
    pool whose tasks bump ``counter`` (a list cell)."""
    def build():
        tp = dtd.taskpool_new()

        def body(es, task):
            counter[0] += 1

        for k in range(n_tasks):
            tp.insert_task(body, (k, VALUE))
        return tp
    return build


def test_local_submit_lifecycle_and_gauges(ctx):
    done = [0]
    with SessionServer(ctx) as srv:
        assert ctx.serve_fairness is srv.fairness
        assert SERVE_TENANTS in ctx.sde.names()
        srv.open_tenant("acme", weight=4)
        names = ctx.sde.names()
        for prefix in (SERVE_INFLIGHT_PREFIX, SERVE_QUOTA_BYTES_PREFIX,
                       SERVE_P99_LATENCY_PREFIX):
            assert f"{prefix}::acme" in names
        subs = [srv.submit("acme", _count_build(ctx, done), ntasks=4)
                for _ in range(3)]
        for sub in subs:
            assert sub.wait(30), "served pool never completed"
            assert sub.error is None
            assert sub.lat_us > 0
        assert done[0] == 12
        st = srv.stats()["tenants"]["acme"]
        assert st["weight"] == 4 and st["pools_done"] == 3
        assert st["inflight_pools"] == 0 and st["queued"] == 0
        assert st["p50_lat_us"] > 0 and st["p99_lat_us"] >= st["p50_lat_us"]
    # close() detaches everything it hooked
    assert ctx.serve_fairness is None
    names = ctx.sde.names()
    assert SERVE_TENANTS not in names
    assert f"{SERVE_INFLIGHT_PREFIX}::acme" not in names


def test_local_admission_errors(ctx):
    srv = SessionServer(ctx)
    try:
        with pytest.raises(AdmissionError, match="unknown tenant"):
            srv.submit("ghost", lambda: None)
        srv.open_tenant("t", max_tasks=2)
        with pytest.raises(AdmissionError, match="max in-flight tasks"):
            srv.submit("t", lambda: None, ntasks=3)
        # idempotent re-open keeps the original caps
        t2 = srv.open_tenant("t", max_tasks=99)
        assert t2.max_tasks == 2
    finally:
        srv.close()
    with pytest.raises(AdmissionError, match="closed"):
        srv.submit("t", lambda: None)


def test_failed_build_releases_admission(ctx):
    """A submission whose build raises must un-charge the tenant's
    in-flight counters (capacity would otherwise leak forever) and
    still drain the queue."""
    done = [0]
    with SessionServer(ctx, admission="queue") as srv:
        srv.open_tenant("t", max_pools=1)

        def boom():
            raise RuntimeError("nope")

        bad = srv.submit("t", boom)
        assert bad.wait(10), "failed build must finish the submission"
        assert bad.error and "build failed" in bad.error
        st = srv.stats()["tenants"]["t"]
        assert st["inflight_pools"] == 0 and st["queued"] == 0
        # capacity actually came back: the next submission admits + runs
        ok = srv.submit("t", _count_build(ctx, done, n_tasks=2), ntasks=2)
        assert ok.wait(30) and ok.error is None
    assert done[0] == 2


def test_abort_releases_admission_and_promotes_queue(ctx):
    """Taskpool.abort (FT eviction) must run the serve abort hook:
    charges release, the submission fails (waiters unblock), and the
    tenant's queued work is promoted."""
    gate = threading.Event()

    def gated_build():
        tp = dtd.taskpool_new()

        def body(es, task):
            gate.wait(10)

        tp.insert_task(body, (0, VALUE))
        return tp

    done2 = [0]
    with SessionServer(ctx, admission="queue") as srv:
        srv.open_tenant("t", max_pools=1)
        sub1 = srv.submit("t", gated_build)
        sub2 = srv.submit("t", _count_build(ctx, done2, n_tasks=2))
        assert srv.stats()["tenants"]["t"]["queued"] == 1
        sub1.taskpool.abort()
        assert sub1.wait(10), "abort must finish the submission"
        assert sub1.error and "abort" in sub1.error
        gate.set()                     # release the parked worker
        assert sub2.wait(30), "queued pool must promote on abort"
        assert sub2.error is None and done2[0] == 2
        st = srv.stats()["tenants"]["t"]
        assert st["inflight_pools"] == 0 and st["queued"] == 0
    gate.set()


class _Tile:
    """Attribute-capable mempool element (owner back-pointer rides it)."""


def test_mempool_free_kicks_queued_submission(ctx):
    """A submission queued on the Mempool-fed byte quota while the
    tenant has ZERO in-flight pools has no _pool_done event to drain
    it — the bound pool's free path must kick re-admission."""
    from parsec_tpu.core.mempool import Mempool
    mp = Mempool(_Tile)
    done = [0]
    with SessionServer(ctx, admission="queue") as srv:
        srv.open_tenant("t", quota_bytes=100)
        srv.bind_mempool("t", mp, item_bytes=60)
        elt = mp.allocate()            # 60 outstanding bytes
        sub = srv.submit("t", _count_build(ctx, done, n_tasks=2),
                         nbytes=50)    # 60 + 50 > 100 -> queued
        assert srv.stats()["tenants"]["t"]["queued"] == 1
        mp.free(elt)                   # headroom appears -> kick drains
        assert sub.wait(30), "mempool free must re-admit queued work"
        assert sub.error is None and done[0] == 2
    assert mp.on_free is None          # close() unhooks the pool


def test_latency_window_knob_sizes_rings(ctx):
    """serve_latency_window must actually size the per-tenant latency
    rings in both the server and the live monitor."""
    with params.cmdline_override("serve_latency_window", "3"):
        srv = SessionServer(ctx)
        try:
            t = srv.open_tenant("t")
            assert t.lat_us.maxlen == 3
        finally:
            srv.close()
        lh = LiveHealth(rank=0)
        assert lh.TENANT_LAT_RING == 3
        for us in (1.0, 2.0, 3.0, 4.0):
            lh.note_tenant_latency("t", us)
        assert lh._tenants["t"]["lat"].maxlen == 3
        assert list(lh._tenants["t"]["lat"]) == [2.0, 3.0, 4.0]


# ---------------------------------------------------------------------- #
# remote client over the AM layer                                        #
# ---------------------------------------------------------------------- #
_REMOTE = {"ctx": None, "hits": 0}


def _remote_build():
    """Module-level so it survives the pickled submit path."""
    tp = dtd.taskpool_new()

    def body(es, task):
        _REMOTE["hits"] += 1

    for k in range(5):
        tp.insert_task(body, (k, VALUE))
    return tp


def _serve_pair(ctx):
    """Server on engine 0 (bound to the real context), client on
    engine 1, with a pump thread draining both engines' progress — the
    role the comm thread plays in a TCP deployment."""
    fabric = LocalFabric(2)
    e0, e1 = fabric.engine(0), fabric.engine(1)
    srv = SessionServer(ctx)
    srv.attach_engine(e0)
    cli = ServeClient(e1, server_rank=0, timeout=30.0)
    stop = threading.Event()

    def _pump():
        while not stop.is_set():
            e0.progress()
            e1.progress()
            time.sleep(0.002)

    th = threading.Thread(target=_pump, daemon=True)
    th.start()
    return srv, cli, e0, e1, stop, th


def test_remote_open_submit_wait_stats(ctx):
    _REMOTE["ctx"], _REMOTE["hits"] = ctx, 0
    srv, cli, _e0, _e1, stop, th = _serve_pair(ctx)
    try:
        msg = cli.open_tenant("acme", weight=8)
        assert msg["tenant"] == "acme" and msg["weight"] == 8
        ticket = cli.submit("acme", _remote_build, ntasks=5)
        done = cli.wait(ticket)          # deferred server-side reply
        assert done["ticket"] == ticket and done["lat_us"] > 0
        assert _REMOTE["hits"] == 5
        st = cli.stats()["tenants"]["acme"]
        assert st["pools_done"] == 1 and st["weight"] == 8
        with pytest.raises(RuntimeError, match="unknown tenant"):
            cli.submit("ghost", _remote_build)
    finally:
        stop.set()
        th.join(5)
        srv.close()


def test_remote_capability_gate(ctx):
    srv, cli, e0, e1, stop, th = _serve_pair(ctx)
    try:
        # client side: a peer that never negotiated "sv" is refused
        # locally, before any bytes move
        e1.serve_to = lambda dst: False
        with pytest.raises(RuntimeError, match="sv capability"):
            cli.open_tenant("acme")
        # server side: the gate answers with a versioned error reply
        del e1.serve_to
        e0.serve_to = lambda src: False
        with pytest.raises(RuntimeError, match="did not negotiate"):
            cli.open_tenant("acme")
    finally:
        stop.set()
        th.join(5)
        srv.close()


def test_serve_client_owns_reply_tag_exclusively():
    """The engine keeps one handler per tag: a second ServeClient
    would silently detach the first, so construction refuses until the
    first is closed; close() also fails parked callers promptly."""
    fabric = LocalFabric(2)
    e1 = fabric.engine(1)
    c1 = ServeClient(e1, server_rank=0, timeout=30.0)
    with pytest.raises(RuntimeError, match="one ServeClient per engine"):
        ServeClient(e1, server_rank=0)
    errs = []

    def _blocked():
        try:
            c1.stats()                 # no server attached: never replies
        except Exception as exc:       # noqa: BLE001
            errs.append(exc)

    th = threading.Thread(target=_blocked, daemon=True)
    th.start()
    time.sleep(0.05)
    c1.close()
    th.join(5)
    assert errs and "closed" in str(errs[0])
    with pytest.raises(RuntimeError, match="closed"):
        c1.stats()
    # the tag is free again: a successor attaches cleanly
    with ServeClient(e1, server_rank=0) as c2:
        assert c2 is not None


# ---------------------------------------------------------------------- #
# tenant-stamped flow contexts -> cross-rank tooling                     #
# ---------------------------------------------------------------------- #
def _tenant_flow_pair():
    fabric = LocalFabric(2)
    engines, profiles = [], []
    for r in range(2):
        eng = fabric.engine(r)
        p = Profile(rank=r)
        eng._obs = CommObs(MetricsRegistry(), profile=p)
        fl = FlowIds(r)
        fl.live = True
        eng._flow = fl
        engines.append(eng)
        profiles.append(p)
    return engines, profiles


def test_tenant_rides_flow_context_and_stitches():
    (e0, e1), (p0, p1) = _tenant_flow_pair()
    e0._flow.tenants = {42: "acme"}       # what attach/ctor install
    got = []
    e1.tag_register(TAG_ACTIVATE, lambda src, pl: got.append(pl))
    e0.send_am(1, TAG_ACTIVATE,
               {"tp_id": 42, "root": 0, "ranks": [1], "edges": {1: []}})
    e0.send_am(1, TAG_ACTIVATE,
               {"tp_id": 99, "root": 0, "ranks": [1], "edges": {1: []}})
    e1.progress()
    assert got[0]["_tr"][4] == "acme"     # owned pool: attributed
    assert got[1]["_tr"][4] is None       # foreign pool: unattributed
    docs = [p0.to_chrome_trace(), p1.to_chrome_trace()]
    edges, unmatched = stitch_flows(load_flow_events(merge_trace_docs(docs)))
    assert unmatched == 0
    tagged = [e for e in edges if e.get("tenant") == "acme"]
    assert len(tagged) == 1
    assert sum(1 for e in edges if "tenant" in e) == 1
    # the offline report narrows to one tenant and rolls it up
    report = analyze(docs, tenant="acme")
    per = report["cross_rank"]["per_tenant"]
    assert set(per) == {"acme"}
    assert per["acme"]["flow_edges"] == 1


# ---------------------------------------------------------------------- #
# live-health attribution                                                #
# ---------------------------------------------------------------------- #
def test_live_health_per_tenant_merge_and_render():
    lh0, lh1 = LiveHealth(rank=0), LiveHealth(rank=1)
    assert "per_tenant" not in lh0.snapshot()   # pre-serve shape intact
    for us in (1000.0, 2000.0, 3000.0):
        lh0.note_tenant_latency("acme", us)
    lh1.note_tenant_latency("acme", 9000.0)
    lh1.note_tenant_latency("bulk", 500.0)
    s0, s1 = lh0.snapshot(), lh1.snapshot()
    assert s0["per_tenant"]["acme"]["pools_done"] == 3
    assert s0["per_tenant"]["acme"]["p99_lat_us"] == 3000.0
    fleet = fleet_health({0: s0, 1: s1})
    acme = fleet["per_tenant"]["acme"]
    assert acme["pools_done"] == 4
    assert acme["p99_lat_us"] == 9000.0         # fleet-worst, not a sum
    text = format_health(fleet)
    assert "acme" in text and "bulk" in text
    # a pre-serve fleet document renders with no tenant section
    pre = fleet_health({0: LiveHealth(rank=0).snapshot()})
    assert "per_tenant" not in pre
    format_health(pre)


# ---------------------------------------------------------------------- #
# knob contract: unset constructs nothing, set implies the monitor      #
# ---------------------------------------------------------------------- #
def test_serve_knob_unset_is_inert(ctx):
    assert ctx.serve_fairness is None
    assert not any(n.startswith("PARSEC::SERVE")
                   for n in ctx.sde.names())
    assert ctx.obs.live is None


def test_wire_capture_serve_bit_identity():
    """The frame-level differential (tests/wire_capture.py, leg F):
    toward a peer that never advertised "sv", a ``serve`` sender with a
    tenant map armed stamps no tenant and sends no serve control frame:
    its data frames are BIT-IDENTICAL to the knob-unset run."""
    from wire_capture import capture_identity

    out = capture_identity()
    assert out["trace_frames_captured"] > 0
    assert out["serve_mixed_version_bit_identical"]


def test_serve_knob_implies_live_monitor():
    with params.cmdline_override("serve", "1"):
        c = parsec_tpu.init(nb_cores=2)
        try:
            assert c.obs.live is not None, \
                "serve=1 must arm obs_live (tenant SLO attribution)"
        finally:
            c.fini()
