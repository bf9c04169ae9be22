"""Test fixture: the knob-unset wire differential (every capability
knob, unset or set toward a peer that never advertised it, leaves the
data frames byte-identical).  Each knob's own test file asserts its key
of the result.
"""
import time

import numpy as np


def capture_identity() -> dict:
    """The knob-unset wire differential of ISSUE 15's acceptance gate:
    a SCRIPTED deterministic message exchange (sequential sends, one
    frame per message, drained between sends so frame order is
    enqueue order) between two fresh TCP engines, with every outbound
    frame captured at the ``_sendall_vec`` seam.  Three legs:

    - A/B: ``obs_flow`` unset twice — the captured DATA frame streams
      must be BYTE-IDENTICAL (the knob-unset wire is deterministic and
      carries no trace bytes);
    - C: ``obs_flow`` SET on rank 0 only — rank 1 (knob unset) never
      advertises ``"tr"``, so rank 0 negotiates DOWN and its data
      frames stay byte-identical to the unset legs (the mixed-version
      contract).  HELLO frames differ by the advertisement (the same
      precedent as the "rs"/"qz" capabilities) and are excluded.
    - D (ISSUE 16): ``obs_live`` SET on rank 0 only — the same
      contract for the streaming health monitor's knob: rank 1 never
      advertises ``"lv"`` (nor ``"tr"``), so neither plain nor
      EXTENDED trace contexts travel and rank 0's data frames stay
      byte-identical to the unset legs.
    - E (ISSUE 17): ``tune_auto`` SET on rank 0 only — the self-tuning
      controller's knob: rank 1 never advertises ``"tn"``, so no
      K_TUNE renegotiation may ever travel and rank 0's data frames
      stay byte-identical to the unset legs (the tune-on leg proves
      the UNSET legs carry no tuning bytes either way).
    - F (ISSUE 18): ``serve`` SET on rank 0 only, with a session
      server's tenant map armed on the flow allocator — rank 1 never
      advertises ``"sv"`` (nor ``"lv"``), so neither tenant-extended
      trace contexts nor serve control frames may travel and rank 0's
      data frames stay byte-identical to the unset legs.
    - G (ISSUE 19): ``xfer_dplane`` SET on rank 0 only — the device
      data plane's knob: rank 1 never advertises ``"dp"``, so the link
      negotiates DOWN to the session wire and rank 0's data frames
      stay byte-identical to the unset legs (no transfer-server
      address exchange, no descriptor envelopes).
    - H (ISSUE 20): ``stage_compile_xrank``'s "xs" capability SET on
      rank 0 only — rank 1 never advertises the process token, so
      rank 0 negotiates DOWN and no cross-rank digest/boundary control
      frames may travel; data frames stay byte-identical to the unset
      legs.
    """
    import threading as _threading
    from contextlib import ExitStack

    from parsec_tpu.comm import tcp as tcpmod
    from parsec_tpu.comm.engine import (TAG_ACTIVATE, TAG_DTD_DATA,
                                        TAG_MEM_PUT)
    from parsec_tpu.comm.tcp import TCPCommEngine, free_ports
    from parsec_tpu.utils.params import params as _params

    chunk = 4096

    def leg(flow_r0, live_r0=False, tune_r0=False, serve_r0=False,
            dplane_r0=False, xstage_r0=False):
        captured = {}
        orig = tcpmod._sendall_vec

        def capturing(sock, pieces):
            body = b"".join(bytes(p) for p in pieces)
            captured.setdefault(
                _threading.current_thread().name, []).append(body)
            orig(sock, pieces)

        ports = free_ports(2)
        eps = [("127.0.0.1", p) for p in ports]
        with ExitStack() as st:
            st.enter_context(_params.cmdline_override(
                "comm_coalesce_max_bytes", "0"))   # one frame/message
            st.enter_context(_params.cmdline_override(
                "comm_chunk_bytes", str(chunk)))
            tcpmod._sendall_vec = capturing
            try:
                engines = [None, None]

                def boot(r):
                    engines[r] = TCPCommEngine(
                        r, eps, obs_flow=(flow_r0 and r == 0),
                        obs_live=(live_r0 and r == 0),
                        tune_auto=(tune_r0 and r == 0),
                        serve=(serve_r0 and r == 0),
                        dplane=(dplane_r0 and r == 0),
                        xstage=(xstage_r0 and r == 0))
                ts = [_threading.Thread(target=boot, args=(r,))
                      for r in (0, 1)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(30)
                e0, e1 = engines
                # the flow allocator would be armed by the obs wiring;
                # arm it directly here (no Context in this scripted leg)
                if flow_r0 or live_r0 or serve_r0:
                    from parsec_tpu.comm.engine import FlowIds
                    e0._flow = FlowIds(0)
                    e0._flow.live = live_r0 or serve_r0
                    if serve_r0:
                        # what SessionServer installs: a pool the
                        # server owns — the stamp may only travel on
                        # a mutually-negotiated "sv" link
                        e0._flow.tenants = {0: "acme"}

                    class _NullObs:
                        def am_sent(self, *a):
                            pass

                        def flow_sent(self, *a):
                            pass
                    e0._obs = _NullObs()
                rng = np.random.RandomState(7)
                small = rng.rand(16, 16)
                big = rng.rand(64, 64)        # > chunk: rides the bulk lane

                def drained(eng, peer):
                    p = eng._peer_to(peer)
                    deadline = time.time() + 10
                    while time.time() < deadline:
                        with p.cond:
                            if not p.ctrl and not p.bulk:
                                return
                        time.sleep(0.002)
                    raise TimeoutError("send queue never drained")

                msgs = [
                    (TAG_ACTIVATE, {"tp_id": 0, "root": 0, "ranks": [1],
                                    "edges": {1: []}, "data": small}),
                    (TAG_DTD_DATA, {"tp_id": 0, "tile": (0, 0), "seq": 1,
                                    "data": small * 2}),
                    (TAG_MEM_PUT, {"tp_id": 0, "coll": "descA",
                                   "args": (1, 0), "data": big}),
                    (TAG_ACTIVATE, {"tp_id": 0, "root": 0, "ranks": [1],
                                    "edges": {1: []}, "data": big + 1}),
                ]
                for tag, payload in msgs:
                    e0.send_am(1, tag, payload)
                    drained(e0, 1)
                # frames rank 0's writer actually put on the wire,
                # HELLO (the capability advertisement) excluded
                frames = []
                for name, bodies in captured.items():
                    if "tcp-send-r0" in name:
                        frames.extend(
                            b for b in bodies
                            if not (len(b) > 8 and b[8] == 3))  # K_HELLO
                e0.fini()
                e1.fini()
                return frames
            finally:
                tcpmod._sendall_vec = orig

    a = leg(False)
    b = leg(False)
    c = leg(True)
    d = leg(False, live_r0=True)
    e = leg(False, tune_r0=True)
    f = leg(False, serve_r0=True)
    g = leg(False, dplane_r0=True)
    h = leg(False, xstage_r0=True)
    return {
        "trace_frames_captured": len(a),
        "trace_unset_bit_identical": bool(a and a == b),
        "trace_mixed_version_bit_identical": bool(a and a == c),
        "live_mixed_version_bit_identical": bool(a and a == d),
        "tune_mixed_version_bit_identical": bool(a and a == e),
        "serve_mixed_version_bit_identical": bool(a and a == f),
        "dplane_mixed_version_bit_identical": bool(a and a == g),
        # ISSUE 20: "xs" SET on rank 0 only — rank 1 never advertises
        # the token, rank 0 negotiates DOWN and no cross-rank control
        # frames may travel; data frames stay byte-identical
        "xstage_mixed_version_bit_identical": bool(a and a == h),
    }
