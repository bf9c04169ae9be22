"""Closed-loop self-tuning controller (ISSUE 17, ``tune/``): the
decision matrix driven with synthetic window digests — deterministic
legs per family (escalation, budget cap, revert memory, hysteresis,
mixed-version peers) plus the knob-unset inertness contract.
"""
import numpy as np
import pytest

import parsec_tpu
from parsec_tpu.tune import (CODEC_COST, CODEC_LADDER, Controller,
                             register_tune_gauges)
from parsec_tpu.utils.params import params
from test_comm_wire import _engines


# ---------------------------------------------------------------------- #
# synthetic actuation targets                                            #
# ---------------------------------------------------------------------- #
class FakeEngine:
    """The transport tuning seams the controller actuates against."""

    def __init__(self, tunable=True):
        self.tunable = tunable
        self.sent = []          # (peer, payload) from tune_send (rx dir)
        self.codecs = {}        # peer -> codec from set_quant_codec (tx)
        self.rx_counts = {}     # peer -> (raw, quant) for rx_quant_ratio

    def tune_to(self, peer):
        return self.tunable

    def tune_send(self, peer, payload):
        self.sent.append((peer, dict(payload)))
        return True

    def set_quant_codec(self, peer, codec):
        self.codecs[peer] = codec
        return True

    def active_quant_codec(self, peer):
        return self.codecs.get(peer)

    def rx_quant_ratio(self, peer):
        return self.rx_counts.get(peer, (0, 0))


class FakeDevice:
    """A device exposing the hill-climbed knobs + the stats the
    controller differences per window."""

    def __init__(self, batch_max=16):
        self.name = "fake0"
        self.batch_max = batch_max
        self.prefetch_depth = 4
        self.stats = {"batches": 0, "batched_tasks": 0,
                      "dispatch_ns": 0, "dispatch_tasks": 0,
                      "prefetch_issued": 0, "prefetch_hits": 0}

    def window(self, batches=0, tasks=0, ns=0, n=0,
               pf_issued=0, pf_hits=0):
        """Advance the cumulative stats by one window's worth."""
        self.stats["batches"] += batches
        self.stats["batched_tasks"] += tasks
        self.stats["dispatch_ns"] += ns
        self.stats["dispatch_tasks"] += n
        self.stats["prefetch_issued"] += pf_issued
        self.stats["prefetch_hits"] += pf_hits


class FakeLive:
    """The subscriber seam's annotate target."""

    def __init__(self):
        self.annotations = []

    def annotate(self, name, args):
        self.annotations.append((name, dict(args)))


def make_ctl(eng=None, devices=(), budget=1e-1, hysteresis=2, **kw):
    live = FakeLive()
    ctl = Controller(0, live, engine=eng, devices=devices,
                     residual_budget=budget, hysteresis=hysteresis, **kw)
    return ctl, live


def slow_bw_digest(win, peer=1, bw=1.0):
    return {"window": win, "links": {}, "bw": {peer: bw}, "fired": ()}


def hot_link_digest(win, src=1, z=9.0):
    return {"window": win,
            "links": {f"R{src}->R0": {"warm": True, "z": z}},
            "bw": {}, "fired": ()}


# ---------------------------------------------------------------------- #
# leg 1: a bandwidth-bound link escalates (both directions)              #
# ---------------------------------------------------------------------- #
def test_tx_bw_floor_escalates_one_rung_per_cooldown():
    eng = FakeEngine()
    ctl, live = make_ctl(eng, budget=1e-1, hysteresis=2)
    walls = []
    for w in range(12):
        ctl.on_window(slow_bw_digest(w))
        walls.append(eng.codecs.get(1))
    # two sustained-slow windows arm the move, then one rung per
    # cooldown period: qbf16 first, qint8 after, never in one jump
    assert walls[0] is None
    assert "qbf16" in walls
    assert eng.codecs[1] == "qint8"
    assert walls.index("qbf16") < walls.index("qint8")
    assert ctl.counts["codec_moves"] == 2
    assert ctl.counts["decisions"] == 2
    names = [n for n, _ in live.annotations]
    assert names.count("tune:codec") == 2
    dirs = {a["dir"] for n, a in live.annotations if n == "tune:codec"}
    assert dirs == {"tx"}


def test_rx_exposed_z_renegotiates_the_sender():
    eng = FakeEngine()
    ctl, live = make_ctl(eng, budget=1e-2, hysteresis=2)
    for w in range(4):
        ctl.on_window(hot_link_digest(w))
    # the rx direction actuates by ASKING the sender (K_TUNE payload),
    # never by touching this rank's own tx codec
    assert eng.sent and eng.sent[0][0] == 1
    assert eng.sent[0][1] == {"op": "codec", "codec": "qbf16"}
    assert eng.codecs == {}
    assert ctl.counts["codec_moves"] == 1


# ---------------------------------------------------------------------- #
# leg 2: the residual budget caps the ladder                             #
# ---------------------------------------------------------------------- #
def test_residual_budget_caps_the_ladder():
    # 1e-2 affords qbf16 (cost 1e-2) but not qint8 (cost 1e-1)
    eng = FakeEngine()
    ctl, _ = make_ctl(eng, budget=1e-2, hysteresis=1)
    assert ctl.max_rung == CODEC_LADDER.index("qbf16")
    for w in range(20):
        ctl.on_window(slow_bw_digest(w))
    assert eng.codecs[1] == "qbf16"        # stuck at the budget's rung
    assert ctl.counts["codec_moves"] == 1
    # zero budget affords nothing: the family is inert
    eng2 = FakeEngine()
    ctl2, live2 = make_ctl(eng2, budget=0.0, hysteresis=1)
    for w in range(10):
        ctl2.on_window(slow_bw_digest(w))
        ctl2.on_window(hot_link_digest(w))
    assert eng2.codecs == {} and eng2.sent == []
    assert ctl2.counts["codec_moves"] == 0
    assert live2.annotations == []


# ---------------------------------------------------------------------- #
# leg 3: a regressing device move is rolled back                         #
# ---------------------------------------------------------------------- #
def test_device_move_reverts_on_objective_regress():
    dev = FakeDevice(batch_max=16)
    ctl, live = make_ctl(devices=(dev,), hysteresis=2)
    # window 0 only establishes the stats baseline (deltas are zero);
    # then 2 windows of sparse occupancy (2 tasks/batch vs max 16) at
    # a healthy 10 us/task objective arm + commit the halving move
    for w in range(3):
        dev.window(batches=10, tasks=20, ns=200_000, n=20)
        ctl.on_window({"window": w, "links": {}, "bw": {}, "fired": ()})
    assert dev.batch_max == 8
    assert ctl.counts["device_moves"] == 1
    # the move is on probation: the objective EWMA now regresses far
    # past regress_pct, so the probation judgment restores the old value
    for w in range(3, 5):
        dev.window(batches=10, tasks=20, ns=2_000_000, n=20)
        ctl.on_window({"window": w, "links": {}, "bw": {}, "fired": ()})
    assert dev.batch_max == 16
    assert ctl.counts["reverts"] == 1
    names = [n for n, _ in live.annotations]
    assert names == ["tune:device", "tune:revert"]
    revert = live.annotations[1][1]
    assert revert["knob"] == "batch_max" and revert["to"] == 16


def test_device_move_sticks_when_objective_holds():
    dev = FakeDevice(batch_max=16)
    ctl, _ = make_ctl(devices=(dev,), hysteresis=2)
    for w in range(6):
        dev.window(batches=10, tasks=20, ns=200_000, n=20)
        ctl.on_window({"window": w, "links": {}, "bw": {}, "fired": ()})
    # steady objective: the halving survives probation and, after the
    # cooldown, the still-sparse signal earns the next halving
    assert dev.batch_max <= 8
    assert ctl.counts["reverts"] == 0


# ---------------------------------------------------------------------- #
# leg 4: hysteresis holds under an oscillating signal                    #
# ---------------------------------------------------------------------- #
def test_oscillating_signal_never_commits_a_move():
    eng = FakeEngine()
    dev = FakeDevice(batch_max=16)
    ctl, live = make_ctl(eng, devices=(dev,), hysteresis=2)
    for w in range(20):
        if w % 2 == 0:      # slow window ...
            ctl.on_window(slow_bw_digest(w))
            dev.window(batches=10, tasks=20, ns=200_000, n=20)
        else:               # ... then a healthy one: streaks never reach 2
            ctl.on_window({"window": w,
                           "links": {"R1->R0": {"warm": True, "z": 0.1}},
                           "bw": {1: 500.0}, "fired": ()})
            dev.window(batches=10, tasks=140, ns=200_000, n=140)
    assert eng.codecs == {} and eng.sent == []
    assert dev.batch_max == 16
    assert ctl.counts["decisions"] == 0
    assert live.annotations == []


# ---------------------------------------------------------------------- #
# leg 5: mixed-version peers are never renegotiated                      #
# ---------------------------------------------------------------------- #
def test_mixed_version_peer_never_renegotiated():
    eng = FakeEngine(tunable=False)      # peer without the "tn" HELLO cap
    ctl, live = make_ctl(eng, budget=1e-1, hysteresis=1)
    for w in range(10):
        ctl.on_window(slow_bw_digest(w))
        ctl.on_window(hot_link_digest(w))
    assert eng.sent == [] and eng.codecs == {}
    assert ctl.counts["codec_moves"] == 0
    assert live.annotations == []


# ---------------------------------------------------------------------- #
# leg 6: knob unset constructs nothing                                   #
# ---------------------------------------------------------------------- #
def test_tune_auto_unset_constructs_no_controller():
    ctx = parsec_tpu.Context(nb_cores=1)
    try:
        assert ctx.obs.tuner is None
        assert ctx.obs.live is None      # tune_auto is what implies it
    finally:
        ctx.fini()


def test_tune_auto_set_constructs_controller_and_gauges():
    with params.cmdline_override("tune_auto", "1"), \
            params.cmdline_override("tune_residual_budget", "1e-1"):
        ctx = parsec_tpu.Context(nb_cores=1)
        try:
            tn = ctx.obs.tuner
            assert tn is not None
            assert tn.max_rung == CODEC_LADDER.index("qint8")
            snap = ctx.sde.snapshot()
            for g in ("PARSEC::TUNE::DECISIONS", "PARSEC::TUNE::REVERTS",
                      "PARSEC::TUNE::OBJECTIVE_US"):
                assert g in snap, f"{g} gauge not registered: missing"
        finally:
            ctx.fini()


def test_wire_capture_tune_bit_identity():
    """The frame-level differential (tests/wire_capture.py, leg E):
    toward a peer that never advertised "tn", a tune_auto sender's data
    frames are BIT-IDENTICAL to the knob-unset run — and the unset legs
    carry no tuning bytes at all."""
    from wire_capture import capture_identity

    out = capture_identity()
    assert out["trace_frames_captured"] > 0
    assert out["trace_unset_bit_identical"]
    assert out["tune_mixed_version_bit_identical"]


# ---------------------------------------------------------------------- #
# the actuators are real: TCP engines renegotiate, the trace keeps it    #
# ---------------------------------------------------------------------- #
def test_controller_actuates_real_tcp_link_and_annotates_merged_trace():
    """The same synthetic windows against a REAL loopback TCP pair that
    negotiated "tn" and a real LiveHealth stream: a slow send bandwidth
    installs the codec on this end (tx); a hot inbound link sends a
    K_TUNE frame and the PEER's encoder climbs (rx); a marked buffer
    then travels quantized; sparse occupancy halves batch_max.  Every
    move is a ``tune:*`` INSTANT that survives the offset-corrected
    merge of the two rank timelines."""
    import json
    import time

    from parsec_tpu.comm import wire
    from parsec_tpu.obs import merge_trace_docs
    from parsec_tpu.obs.live import LiveHealth
    from parsec_tpu.obs.spans import HEALTH_STREAM_TID
    from parsec_tpu.profiling.trace import Profile

    e0, e1 = _engines(2, tune_auto=True, chunk_bytes=1 << 14)
    try:
        deadline = time.time() + 10
        while time.time() < deadline and not (e0.tune_to(1)
                                              and e1.tune_to(0)):
            time.sleep(0.005)
        assert e0.tune_to(1) and e1.tune_to(0), '"tn" never negotiated'
        p0, p1 = Profile(rank=0), Profile(rank=1)
        live = LiveHealth(0, stream=p0.stream(HEALTH_STREAM_TID, "health"))
        dev = FakeDevice(batch_max=16)
        ctl = Controller(0, live, engine=e0, devices=(dev,),
                         residual_budget=1e-1, hysteresis=1)
        assert e0.active_quant_codec(1) is None
        assert e1.active_quant_codec(0) is None
        # tx: this end's own send bandwidth under the floor
        ctl.on_window(slow_bw_digest(0))
        assert e0.active_quant_codec(1) == "qbf16"
        # rx: the inbound link is hot -> ask the sender over the wire
        for w in range(1, 8):
            ctl.on_window(hot_link_digest(w))
            if ctl.counts["codec_moves"] >= 2:
                break
        deadline = time.time() + 10
        while time.time() < deadline \
                and e1.active_quant_codec(0) is None:
            time.sleep(0.005)
        assert e1.active_quant_codec(0) == "qbf16"
        # the renegotiated codec carries a marked bulk float buffer
        got = []
        e0.tag_register(700, lambda src, p: got.append(p))
        arr = np.random.RandomState(5).rand(1 << 15)
        e1.send_am(0, 700, {"arr": arr, "_qz_ok": True})
        deadline = time.time() + 30
        while time.time() < deadline and not got:
            if not e0.progress():
                time.sleep(0.0005)
        np.testing.assert_array_equal(np.asarray(got[0]["arr"]),
                                      wire.qdq_array(arr, "qbf16"))
        assert e1.wire_stats["bufs_quantized"] == 1
        assert e0.rx_quant_ratio(1)[0] == arr.nbytes
        # device family: sparse occupancy halves batch_max
        for w in range(8, 12):
            dev.window(batches=10, tasks=20, ns=200_000, n=20)
            ctl.on_window({"window": w, "links": {}, "bw": {},
                           "fired": ()})
        assert dev.batch_max < 16
        assert ctl.counts["codec_moves"] >= 2
        assert ctl.counts["device_moves"] >= 1
    finally:
        e0.fini()
        e1.fini()
    d0, d1 = p0.to_chrome_trace(), p1.to_chrome_trace()
    d1.setdefault("metadata", {})["clock_offsets_us"] = json.dumps(
        {"0": -1500.0})   # rank 1's clock reads 1.5 ms ahead
    merged = merge_trace_docs([d0, d1])
    annos = [e for e in merged["traceEvents"]
             if e.get("ph") == "i" and e.get("tid") == HEALTH_STREAM_TID
             and str(e.get("name", "")).startswith("tune:")]
    names = [e["name"] for e in annos]
    assert names.count("tune:codec") == ctl.counts["codec_moves"]
    assert names.count("tune:device") == ctl.counts["device_moves"]
    assert {e["args"]["dir"] for e in annos
            if e["name"] == "tune:codec"} == {"tx", "rx"}


# ---------------------------------------------------------------------- #
# rx de-escalation: a codec that shows no win steps back down            #
# ---------------------------------------------------------------------- #
def test_rx_codec_without_win_steps_back_down():
    eng = FakeEngine()
    ctl, live = make_ctl(eng, budget=1e-2, hysteresis=1)
    ctl.on_window(hot_link_digest(0))
    assert eng.sent[-1][1]["codec"] == "qbf16"
    # the requested codec never moves a quantized byte: after
    # 2*hysteresis idle windows the controller walks it back
    for w in range(1, 6):
        ctl.on_window({"window": w, "links": {}, "bw": {}, "fired": ()})
    assert eng.sent[-1][1] == {"op": "codec", "codec": None}
    downs = [a for n, a in live.annotations
             if n == "tune:codec" and a["why"] == "no win"]
    assert downs and downs[-1]["codec"] == "lossless"


def test_rx_codec_with_real_win_is_kept():
    eng = FakeEngine()
    ctl, _ = make_ctl(eng, budget=1e-2, hysteresis=1)
    ctl.on_window(hot_link_digest(0))
    raw = quant = 0
    for w in range(1, 8):
        raw += 100_000
        quant += 25_000          # 4x compression: a clear win
        eng.rx_counts[1] = (raw, quant)
        ctl.on_window({"window": w, "links": {}, "bw": {}, "fired": ()})
    assert eng.sent[-1][1]["codec"] == "qbf16"   # never de-escalated
    assert len(eng.sent) == 1
