"""Cross-rank SPMD stages: compile across the wire (ISSUE 20 tentpole).

PR 12's stage compiler fuses a wave-front stage per rank; PR 6's lane
already proves every in-process rank can sit on one jax mesh.  This
module composes the two: when ``stage_compile_xrank`` is on and a
planned wave-front stage spans RANKS, the participating ranks lower
the whole (level, class) wave into ONE ``shard_map`` program over a
global one-axis mesh built from their lane devices
(``parallel.mesh.xrank_mesh`` over ``wave_dist.lane_device_pool``).
Inter-rank dependency edges — activations that today serialize a tile
over the wire — become an in-program collective: each rank's member
rows ride its own mesh position, the cross-rank boundary tiles are
stacked producer-major and ``all_gather``'d over the rank axis, and a
traced index argument routes every boundary-fed flow to its gathered
row.  The gather is pure data movement (no arithmetic — a psum of
one-hot stacks would flip ``-0.0 + 0.0`` to ``+0.0`` and break the
bit-exactness contract), so the compiled wave remains bit-identical
to the interpreted runtime.

The wire then carries CONTROL ONLY for those edges: a producer whose
every consumer edge lands in a cross-rank wave parks the device
payload in the process-global :class:`XStore` and sends the activation
message without ``data``/``handle``/``xfer`` (the ``"xs"`` key names
the parked entry); the consumer rank pulls the SAME array object at
delivery.  Pull-at-delivery is what makes the whole ladder safe: every
rank holds real payloads before its stage dispatches, so any
downstream failure — build error, peer decline, rendezvous timeout —
falls back to the rank-local fused path with nothing lost.

Negotiation mirrors the ``"hb"``/``"rs"``/``"dp"`` capabilities: the
TCP HELLO advertises ``"xs"`` with a per-process random token, and a
peer negotiates UP only when the tokens are EQUAL — token equality
proves both ranks live in one process and therefore share the XLA
device pool a cross-rank mesh needs.  Mixed-version peers, separate
processes, and knob-unset peers all keep today's activation path
bit-for-bit.  Before any wave dispatches, the participants exchange a
digest of the whole cross-rank plan (the ``xfer/plan.py`` contract)
and FAIL LOUDLY on divergence.

Dispatch is a process-global rendezvous keyed (digest, install epoch,
wave id): each participating rank deposits its member blocks (plus the
boundary payloads it consumes), the LAST depositor assembles the
global arrays and runs the cached program, and every rank extracts its
own shard rows.  A rank that downgrades or fails DECLINES the
rendezvous so peers immediately fall back; a rank that never arrives
trips the ``stage_xrank_timeout`` clock.  The fallback ladder is
cross-rank -> per-rank sharded -> fused -> interpreted, one stage at a
time (``XSTAGE_FALLBACKS`` counts every planned wave that left the
cross-rank path).
"""
from __future__ import annotations

import hashlib
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..comm.engine import TAG_USER_BASE
from ..utils import logging as plog
from ..utils.params import params

__all__ = ["XWave", "plan_xwaves", "xwaves_digest", "XSTORE",
           "xs_negotiated", "install_xrank", "dispatch_xrank",
           "decline_rec", "TAG_XSTAGE"]

#: cross-rank stage-plan digest exchange (the xfer/plan.py idiom);
#: +117 sits clear of TAG_REDIST (+111) and the below-base tags
TAG_XSTAGE = TAG_USER_BASE + 117

#: declared lock discipline (analysis/lock_check.py)
_GUARDED_BY = {
    "_Inbox.msgs": "lock",
    "_XStore.entries": "lock",
    "_Rendezvous.deposits": "_rdv_cond",
    "_Rendezvous.declined": "_rdv_cond",
    "_Rendezvous.taken": "_rdv_cond",
    "_Rendezvous.result": "_rdv_cond",
    "_Rendezvous.error": "_rdv_cond",
}


class XWave:
    """One planned cross-rank wave: a (level, class) wave front whose
    members span several ranks, aligned across every participant."""

    __slots__ = ("wave_id", "level", "class_name", "ranks",
                 "members_by_rank", "n_max", "boundary", "feeds",
                 "my_stage_index", "my_info")

    def __init__(self, wave_id: int, level: int, class_name: str,
                 ranks: Tuple[int, ...],
                 members_by_rank: Dict[int, Tuple],
                 boundary: Tuple, feeds: Dict[int, Tuple]) -> None:
        self.wave_id = wave_id
        self.level = level
        self.class_name = class_name
        self.ranks = ranks                      # sorted participants
        #: rank -> member keys in stage order (ragged: padded to n_max)
        self.members_by_rank = members_by_rank
        self.n_max = max(len(m) for m in members_by_rank.values())
        #: dedup'd cross-rank edges: ((prod_rank, prod_key, flow), ...)
        self.boundary = boundary
        #: rank -> per-member tuple of (flow_pos, boundary_index) pairs
        self.feeds = feeds
        #: this rank's matching plan stage (runtime wiring; NOT part of
        #: the digest — per-rank by construction)
        self.my_stage_index: Optional[int] = None
        self.my_info: Optional[Any] = None      # WavefrontInfo


def xwaves_digest(waves: List[XWave]) -> str:
    """sha1 over the SPMD-consistent wave content: every rank derives
    the same plan from the same spec/knobs, so the digests must agree
    — asserted before any wave dispatches (the xfer/plan.py loud-
    failure contract)."""
    canon = [(w.wave_id, w.level, w.class_name, w.ranks, w.n_max,
              tuple(sorted((r, w.members_by_rank[r]) for r in w.ranks)),
              w.boundary,
              tuple(sorted((r, w.feeds[r]) for r in w.ranks)))
             for w in waves]
    return hashlib.sha1(repr(canon).encode()).hexdigest()


# ---------------------------------------------------------------------- #
# planner pass: replay the wavefront partition per rank and align        #
# ---------------------------------------------------------------------- #
def plan_xwaves(tp, plan, max_tasks: int) -> None:
    """Fill ``plan.xwaves`` (the cross-rank waves this plan dispatches
    through :func:`dispatch_xrank`) and ``plan.xwave_report`` (one
    entry per (level, class) wave group: spanning ranks, boundary-edge
    count and collective kind, or the reason it stays rank-local — the
    ``parsec_lint --lower-report`` cross-rank column).

    Eligibility failures recorded here are PLAN verdicts, not
    fallbacks: only a planned wave that later leaves the cross-rank
    path at build/dispatch time counts in ``XSTAGE_FALLBACKS``."""
    from .lower import _producer_locals, build_layout, spec_codes
    from .plan import Stage, _instance_compilable
    from .sharded import wavefront_info

    nb = tp.nb_ranks
    verdicts = plan.verdicts
    codes = spec_codes(tp)
    class_ast = {tc.ast.name: tc.ast for tc in tp.task_classes}
    my_rank = tp.rank

    rank_of: Dict[Tuple, int] = {}
    ok_by_rank: List[set] = [set() for _ in range(nb)]
    for inst in plan.order:
        r = inst.tc.rank_of_instance(inst.env)
        rank_of[inst.key] = r
        if 0 <= r < nb and _instance_compilable(
                tp, inst, verdicts[inst.tc.ast.name], r):
            ok_by_rank[r].add(inst.key)

    by_level: Dict[int, List[Any]] = {}
    for inst in plan.order:
        by_level.setdefault(plan.levels[inst.key], []).append(inst)

    my_stage_of = {}
    for st in plan.stages:
        my_stage_of[tuple(m.key for m in st.members)] = st.index

    waves: List[XWave] = []
    report: List[Tuple[int, str, str]] = []

    def note(lv: int, cls: str, text: str) -> None:
        report.append((lv, cls, text))

    for lv in sorted(by_level):
        # per-class member lists per rank, in plan (stage) order — the
        # exact grouping plan_stages' wavefront branch produces
        per_class: Dict[str, Dict[int, List[Any]]] = {}
        for inst in by_level[lv]:
            r = rank_of[inst.key]
            if not (0 <= r < nb) or inst.key not in ok_by_rank[r]:
                continue
            per_class.setdefault(inst.tc.ast.name, {}) \
                .setdefault(r, []).append(inst)
        for cls in sorted(per_class):
            groups = per_class[cls]
            ranks = tuple(sorted(groups))
            if len(ranks) < 2:
                note(lv, cls, f"rank-local (spans {len(ranks)} rank)")
                continue
            if any(len(g) > max_tasks for g in groups.values()):
                note(lv, cls, "a rank's wave exceeds "
                     "stage_compile_max_tasks (chunk split: waves "
                     "would misalign across ranks)")
                continue
            wave = _plan_one_wave(
                tp, plan, lv, cls, ranks, groups, rank_of, class_ast,
                codes, my_rank, my_stage_of, len(waves),
                build_layout, wavefront_info, _producer_locals, note)
            if wave is not None:
                waves.append(wave)

    plan.xwaves = waves
    plan.xwave_report = report


def _plan_one_wave(tp, plan, lv, cls, ranks, groups, rank_of, class_ast,
                   codes, my_rank, my_stage_of, wave_id,
                   build_layout, wavefront_info, _producer_locals,
                   note) -> Optional[XWave]:
    from .plan import Stage
    members_by_rank: Dict[int, Tuple] = {}
    infos: Dict[int, Any] = {}
    boundary_index: Dict[Tuple, int] = {}
    boundary: List[Tuple] = []
    feeds: Dict[int, Tuple] = {}
    for r in ranks:
        insts = groups[r]
        st = Stage(-1)
        for inst in insts:
            st.add(inst, lv)
        try:
            layout_r = build_layout(tp, plan, st)
            info_r = wavefront_info(tp, st, layout_r, codes)
        except Exception as exc:  # noqa: BLE001 - plan verdict, not error
            note(lv, cls, f"rank {r}: layout failed ({exc})")
            return None
        if info_r is None:
            note(lv, cls, f"rank {r}: not wavefront-lowerable "
                 "(shared slot / NEW binding / intra-wave edge)")
            return None
        if "es_rank" in info_r.code.co_names:
            # the shard_map body is traced ONCE for all ranks: a body
            # reading es_rank would see one rank's value everywhere
            note(lv, cls, "body reads es_rank — per-rank values can't "
                 "ride one traced program")
            return None
        if not _uniform_mem_shapes(tp, info_r, layout_r):
            note(lv, cls, f"rank {r}: ragged member tile shapes")
            return None
        members_by_rank[r] = tuple(i.key for i in insts)
        infos[r] = info_r
        rfeeds = []
        for i, inst in enumerate(insts):
            pairs = []
            for (j, pk, pfl) in _member_boundary(
                    inst, rank_of, r, class_ast, _producer_locals):
                bk = (rank_of[pk], pk, pfl)
                b = boundary_index.get(bk)
                if b is None:
                    b = boundary_index[bk] = len(boundary)
                    boundary.append(bk)
                pairs.append((j, b))
            rfeeds.append(tuple(pairs))
        feeds[r] = tuple(rfeeds)
    if any(pr not in ranks for (pr, _pk, _fl) in boundary):
        # a boundary producer on a NON-participating rank has no mesh
        # position to source the gather from
        note(lv, cls, "boundary producer outside the wave's rank set")
        return None
    wave = XWave(wave_id, lv, cls, ranks, members_by_rank,
                 tuple(boundary), feeds)
    if my_rank in ranks:
        wave.my_stage_index = my_stage_of.get(members_by_rank[my_rank])
        wave.my_info = infos[my_rank]
        if wave.my_stage_index is None:
            note(lv, cls, "wave does not match a planned stage on this "
                 "rank")
            return None
    note(lv, cls, f"cross-rank: {len(ranks)} rank(s), "
         f"{len(boundary)} boundary edge(s), all-gather")
    return wave


def _member_boundary(inst, rank_of, r, class_ast, _producer_locals):
    """Cross-rank act-fed flows of one member: [(flow_pos, prod_key,
    prod_flow)] — the exact first-applicable binding walk the fused
    program (lower.build_stage_fn) and wavefront_info perform."""
    out = []
    nonctl = [f for f in inst.tc.ast.flows if not f.is_ctl]
    for j, f in enumerate(nonctl):
        for d in f.deps_in():
            t = d.resolve(inst.env)
            if t is None:
                continue
            if t.kind == "task":
                pk = (t.task_class, _producer_locals(
                    class_ast, t.task_class,
                    tuple(a(inst.env) for a in t.args)))
                pr = rank_of.get(pk)
                if pr is not None and pr != r:
                    out.append((j, pk, t.flow))
            break
    return out


def _uniform_mem_shapes(tp, info, layout) -> bool:
    """Plan-time ragged check over MEMORY-bound slots: member-major
    stacking needs one tile shape per flow.  Activation payload shapes
    are only known at dispatch; the assembler re-checks them."""
    n_mem = len(layout.mem_slots)
    for j in range(info.nargs):
        shapes = set()
        for i in range(info.n):
            slot = info.arg_slots[i][j]
            if slot < n_mem:
                (coll_name, coords), _a = layout.mem_slots[slot]
                coll = tp.global_env[coll_name]
                shapes.add(tuple(coll.tile_shape(*coords)))
        if len(shapes) > 1:
            return False
    return True


# ---------------------------------------------------------------------- #
# XStore: in-process payload parking for control-only activations        #
# ---------------------------------------------------------------------- #
class _XStore:
    """Process-global parked payloads for cross-rank waves.  The
    producer deposits once with a refcount of the receiving-rank
    count; each consumer rank takes exactly once at delivery (the
    transport's K_SEQ dedup makes replays invisible here)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.entries: Dict[Tuple, List] = {}   # key -> [payload, refs]

    def put(self, key: Tuple, payload: Any, refs: int) -> None:
        with self.lock:
            self.entries[key] = [payload, refs]

    def take(self, key: Tuple) -> Any:
        with self.lock:
            ent = self.entries.get(key)
            if ent is None:
                return None
            ent[1] -= 1
            payload = ent[0]
            if ent[1] <= 0:
                del self.entries[key]
            return payload

    def __len__(self) -> int:
        with self.lock:
            return len(self.entries)


XSTORE = _XStore()

_xs_seq_lock = threading.Lock()  # lock: guards module-global _xs_seq counter, not a class field
_xs_seq = 0


def xstore_key(rank: int, tp_id: int) -> Tuple:
    """A fresh park key: unique per process, prefixed with the sender
    identity so a key printed in an error names its origin."""
    global _xs_seq
    with _xs_seq_lock:
        _xs_seq += 1
        return ("xs", rank, tp_id, _xs_seq)


def xs_negotiated(ce, peer: int) -> bool:
    """Did ``peer`` negotiate the ``"xs"`` capability?  TCP engines
    answer from the HELLO token exchange (``xstage_to``); an engine
    without the accessor is an in-process fabric whose ranks are
    co-resident by construction — the knob alone gates it there."""
    fn = getattr(ce, "xstage_to", None)
    if fn is not None:
        return bool(fn(peer))
    return bool(params.get_or("stage_compile_xrank", "bool", False))


def stage_donation_active(tp) -> bool:
    """Is donate-by-default (ISSUE 20c) live on this pool's compiler?
    By-reference payload shipping must defensively copy while it is —
    a later donated stage would otherwise invalidate the shipped
    buffer under the consumer."""
    sc = getattr(tp, "_stagec", None)
    return sc is not None and getattr(sc, "_donate_default", False)


# ---------------------------------------------------------------------- #
# digest exchange (the xfer/plan.py inbox idiom)                         #
# ---------------------------------------------------------------------- #
class _Inbox:
    """Per-engine TAG_XSTAGE inbox: FIFO per (src, kind) — pool
    installs are SPMD-ordered, so the k-th take on one rank pairs with
    the k-th send from the peer."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.msgs: Dict[Tuple, List[Dict]] = {}

    def on_msg(self, src: int, payload: Dict) -> None:
        key = (src, payload.get("kind"))
        with self.lock:
            self.msgs.setdefault(key, []).append(payload)

    def take(self, key: Tuple) -> Optional[Dict]:
        with self.lock:
            q = self.msgs.get(key)
            if not q:
                return None
            return q.pop(0)


def _inbox_of(ce) -> _Inbox:
    box = getattr(ce, "_xstage_inbox", None)
    if box is None:
        box = _Inbox()
        ce._xstage_inbox = box
        ce.tag_register(TAG_XSTAGE, box.on_msg)
    return box


def _wait_take(ce, box: _Inbox, key: Tuple, timeout: float) -> Dict:
    deadline = time.monotonic() + timeout
    while True:
        msg = box.take(key)
        if msg is not None:
            return msg
        if time.monotonic() > deadline:
            raise TimeoutError(f"xstage digest from rank {key[0]} "
                               f"not received within {timeout}s")
        ce.progress()
        time.sleep(0.0005)


def _exchange_digest(ce, peers: List[int], digest: str, epoch: int,
                     timeout: float) -> bool:
    """Send my (digest, epoch) to every spanning peer and await
    theirs.  A DIGEST mismatch is a diverged plan — fail loudly (the
    run_redistribution contract).  A missing or epoch-skewed peer
    negotiates the pool DOWN to rank-local stages instead."""
    box = _inbox_of(ce)
    for p in peers:
        ce.send_am(p, TAG_XSTAGE,
                   {"kind": "cfg", "digest": digest, "epoch": epoch})
    for p in peers:
        try:
            msg = _wait_take(ce, box, (p, "cfg"), timeout)
        except TimeoutError:
            plog.warning(
                "stagec xrank: rank %d sent no plan digest within %gs; "
                "cross-rank stages disabled for this pool", p, timeout)
            return False
        if msg.get("digest") != digest:
            raise RuntimeError(
                f"stagec xrank: cross-rank stage plan diverges from "
                f"rank {p} (digest {msg.get('digest')!r} != {digest!r})"
                " — ranks disagree on the wave partition")
        if msg.get("epoch") != epoch:
            plog.warning(
                "stagec xrank: install epoch skew vs rank %d (%s != "
                "%d); cross-rank stages disabled for this pool",
                p, msg.get("epoch"), epoch)
            return False
    return True


#: (digest, rank) -> install count; every rank installs the SPMD-same
#: pool sequence, so the k-th install of a digest agrees process-wide
_epoch_lock = threading.Lock()  # lock: guards module-global _install_counts, not a class field
_install_counts: Dict[Tuple[str, int], int] = {}


def _install_epoch(digest: str, rank: int) -> int:
    with _epoch_lock:
        c = _install_counts.get((digest, rank), 0) + 1
        _install_counts[(digest, rank)] = c
        return c


# ---------------------------------------------------------------------- #
# install: wire waves onto stage recs, exchange the digest               #
# ---------------------------------------------------------------------- #
def install_xrank(compiler) -> bool:
    """Attach the plan's cross-rank waves to this compiler: negotiate
    ``"xs"`` with every spanning peer, exchange and assert the plan
    digest, wire each wave onto its stage rec, and publish the
    producer-side elision target set (``tp._xs_targets``).  False
    leaves every stage rank-local (never an error)."""
    tp = compiler.tp
    waves: List[XWave] = list(getattr(compiler.plan, "xwaves", ()) or ())
    if not waves:
        return False
    ce = getattr(getattr(tp, "comm", None), "ce", None)
    if ce is None:
        return False
    me = tp.rank
    peers = sorted({r for w in waves for r in w.ranks} - {me})
    if not peers:
        return False
    for p in peers:
        if not xs_negotiated(ce, p):
            plog.debug.verbose(
                2, "stagec xrank: peer %d did not negotiate 'xs' "
                "(mixed version or separate process); rank-local "
                "stages", p)
            return False
    timeout = _timeout()
    digest = xwaves_digest(waves)
    epoch = _install_epoch(digest, me)
    _purge_stale(digest, epoch)
    if not _exchange_digest(ce, peers, digest, epoch, timeout):
        return False
    compiler._xrank = (digest, epoch)
    targets = set()
    wired = 0
    for w in waves:
        for mks in w.members_by_rank.values():
            targets.update(mks)
        if me not in w.ranks:
            continue
        rec = compiler._rec_by_index.get(w.my_stage_index)
        if rec is not None and w.my_info is not None and \
                tuple(m.key for m in rec.stage.members) \
                == w.members_by_rank[me]:
            rec.xwave = w
            wired += 1
        else:
            # peers will rendezvous this wave: decline NOW so they
            # fall back instead of running out the clock
            _decline(digest, epoch, w, me)
            compiler.stats["xstage_fallbacks"] += 1
    tp._xs_targets = targets
    plog.debug.verbose(
        2, "stagec xrank: %s rank %d joined %d cross-rank wave(s) "
        "(%d wired) with rank(s) %s", tp.name, me, len(waves), wired,
        peers)
    return True


def _timeout() -> float:
    try:
        return float(params.get_or("stage_xrank_timeout", "string",
                                   "60") or 60)
    except (TypeError, ValueError):
        return 60.0


# ---------------------------------------------------------------------- #
# rendezvous: deposit / assemble / extract                               #
# ---------------------------------------------------------------------- #
class _Rendezvous:
    """One wave's meeting point, keyed (digest, epoch, wave_id)."""

    def __init__(self, ranks: Tuple[int, ...]) -> None:
        self.ranks = frozenset(ranks)
        # every entry shares the MODULE condition (entries are created
        # and reaped under it); the instance alias is the declared
        # guard handle for the fields below (_GUARDED_BY)
        self._rdv_cond = _rdv_cond
        self.deposits: Dict[int, Dict] = {}
        self.declined: set = set()
        self.taken: set = set()
        self.result: Optional[Dict] = None
        self.error: Optional[str] = None


_rdv_cond = threading.Condition()
_rdv: Dict[Tuple, _Rendezvous] = {}


def _purge_stale(digest: str, epoch: int) -> None:
    with _rdv_cond:
        for k in [k for k in _rdv
                  if k[0] == digest and k[1] < epoch]:
            _rdv.pop(k)
        _rdv_cond.notify_all()


def _ent(key: Tuple, ranks: Tuple[int, ...]) -> _Rendezvous:
    with _rdv_cond:
        ent = _rdv.get(key)
        if ent is None:
            ent = _rdv[key] = _Rendezvous(ranks)
        return ent


def _gc_locked(key: Tuple, ent: _Rendezvous) -> None:  # holds: ent._rdv_cond
    if ent.taken | ent.declined >= ent.ranks:
        _rdv.pop(key, None)


def _decline(digest: str, epoch: int, wave: XWave, rank: int) -> None:
    ent = _ent((digest, epoch, wave.wave_id), wave.ranks)
    with ent._rdv_cond:
        ent.declined.add(rank)
        if ent.error is None:
            ent.error = f"rank {rank} declined the cross-rank stage"
        _gc_locked((digest, epoch, wave.wave_id), ent)
        ent._rdv_cond.notify_all()


def decline_rec(compiler, rec) -> None:
    """This rank leaves ``rec``'s wave (downgrade / build failure):
    tell the rendezvous so waiting peers fall back NOW."""
    wave = getattr(rec, "xwave", None)
    xr = getattr(compiler, "_xrank", None)
    if wave is None or xr is None:
        return
    _decline(xr[0], xr[1], wave, compiler.tp.rank)


def dispatch_xrank(compiler, rec, arrays: List[Any]):
    """Run ``rec``'s stage as its cross-rank wave's shard of ONE
    shard_map program.  Returns ``(tile_outs, edge_outs)`` in layout
    order; raises to send the caller down the rank-local ladder (the
    rendezvous is declined/errored first, so peers never hang)."""
    wave: XWave = rec.xwave
    info = wave.my_info
    me = compiler.tp.rank
    xr = compiler._xrank
    key = (xr[0], xr[1], wave.wave_id)
    try:
        deposit = _make_deposit(compiler, wave, info, arrays, me)
    except Exception:
        _decline(xr[0], xr[1], wave, me)
        raise
    ce = getattr(getattr(compiler.tp, "comm", None), "ce", None)
    run_build = False
    ent = _ent(key, wave.ranks)
    with ent._rdv_cond:
        ent.deposits[me] = deposit
        if ent.error is None and len(ent.deposits) == len(wave.ranks):
            run_build = True
            deposits = ent.deposits
    if run_build:
        try:
            result = _assemble_and_run(compiler, wave, info, deposits)
        except Exception as exc:  # noqa: BLE001 - shared verdict
            with ent._rdv_cond:
                if ent.error is None:
                    ent.error = (f"assembly failed on rank {me}: "
                                 f"{type(exc).__name__}: {exc}")
                ent._rdv_cond.notify_all()
            _take_and_gc(key, ent, me)
            raise
        with ent._rdv_cond:
            ent.result = result
            ent._rdv_cond.notify_all()
    else:
        _await_result(ent, ce, wave, me, key)
    with ent._rdv_cond:
        err, result = ent.error, ent.result
    _take_and_gc(key, ent, me)
    if err is not None:
        raise RuntimeError(f"cross-rank wave {wave.wave_id} "
                           f"({wave.class_name} level {wave.level}): "
                           f"{err}")
    return _extract(compiler, wave, info, result, me)


def _take_and_gc(key: Tuple, ent: _Rendezvous, me: int) -> None:
    with ent._rdv_cond:
        ent.taken.add(me)
        _gc_locked(key, ent)
        ent._rdv_cond.notify_all()


def _await_result(ent: _Rendezvous, ce, wave: XWave, me: int,
                  key: Tuple) -> None:
    """Wait for the assembler (or an error) while keeping the comm
    engine progressing — peer deposits may arrive through it."""
    timeout = _timeout()
    deadline = time.monotonic() + timeout
    while True:
        with ent._rdv_cond:
            if ent.result is not None or ent.error is not None:
                return
            ent._rdv_cond.wait(0.01)
            if ent.result is not None or ent.error is not None:
                return
        if ce is not None:
            try:
                ce.progress()
            except Exception:  # noqa: BLE001 - progress is best-effort
                pass
            dead = getattr(ce, "dead_peers", None) or ()
            gone = [r for r in wave.ranks if r != me and r in dead]
            if gone:
                with ent._rdv_cond:
                    if ent.error is None:
                        ent.error = (f"peer rank(s) {gone} died before "
                                     f"the rendezvous completed")
                    ent._rdv_cond.notify_all()
                return
        if time.monotonic() > deadline:
            with ent._rdv_cond:
                if ent.result is None and ent.error is None:
                    ent.error = (f"rendezvous timed out after "
                                 f"{timeout}s (stage_xrank_timeout)")
                    ent._rdv_cond.notify_all()
            return


def _make_deposit(compiler, wave: XWave, info, arrays: List[Any],
                  me: int) -> Dict:
    """My shard's contribution: per-flow member blocks in stage order,
    the boundary payloads I consume, and my locals rows."""
    n_me = len(wave.members_by_rank[me])
    blocks = [[arrays[info.arg_slots[i][j]] for i in range(n_me)]
              for j in range(info.nargs)]
    donate_live = getattr(compiler, "_donate_default", False) \
        or getattr(compiler, "_donate_on", False)
    bnd: Dict[int, Any] = {}
    for i, pairs in enumerate(wave.feeds[me]):
        for (j, b) in pairs:
            if b not in bnd:
                arr = arrays[info.arg_slots[i][j]]
                if donate_live:
                    # a donated stage elsewhere in the process could
                    # invalidate this buffer before the assembler
                    # placed it — pay one defensive device copy
                    import jax.numpy as jnp
                    arr = jnp.array(arr, copy=True)
                bnd[b] = arr
    loc = np.asarray(info.local_vals, np.int32) \
        if info.local_names else None
    return {"rank": me, "blocks": blocks, "bnd": bnd, "locals": loc}


def _assemble_and_run(compiler, wave: XWave, info,
                      deposits: Dict[int, Dict]) -> Dict:
    """LAST depositor's job: build the global sharded arrays over the
    cross-rank lane mesh, fetch-or-build the cached program, run it,
    and publish the global outputs for every rank to extract from."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from ..devices.batching import cached_stage_callable
    from ..dsl.ptg.wave_dist import lane_device_pool
    from ..parallel.mesh import xrank_mesh

    R = len(wave.ranks)
    n_max, nargs = wave.n_max, info.nargs
    B = len(wave.boundary)
    pool = lane_device_pool(compiler.tp.nb_ranks)
    if pool is None or len(pool) < compiler.tp.nb_ranks:
        raise RuntimeError("no lane device pool for the cross-rank "
                           "mesh")
    lane_devs = [pool[r] for r in wave.ranks]
    if len({id(d) for d in lane_devs}) != len(lane_devs):
        raise RuntimeError("lane devices are not distinct per rank")

    # flow shapes/dtypes: uniform member-major stacking, checked here
    # (activation payload shapes are only known now)
    shapes, dtypes = [], []
    for j in range(nargs):
        sh = dt = None
        for r in wave.ranks:
            for a in deposits[r]["blocks"][j]:
                if sh is None:
                    sh, dt = tuple(a.shape), np.dtype(a.dtype)
                elif tuple(a.shape) != sh or np.dtype(a.dtype) != dt:
                    raise RuntimeError(
                        f"ragged flow {info.flow_names[j]!r} across "
                        f"the wave: {tuple(a.shape)} vs {sh}")
        shapes.append(sh)
        dtypes.append(dt)

    bnd_flows = tuple(sorted({j for r in wave.ranks
                              for pairs in wave.feeds[r]
                              for (j, _b) in pairs}))
    tshape, tdt = (), np.dtype(np.float32)
    if B:
        payloads: Dict[int, Any] = {}
        for r in wave.ranks:
            payloads.update(deposits[r]["bnd"])
        missing = [b for b in range(B) if b not in payloads]
        if missing:
            raise RuntimeError(f"boundary entries {missing} have no "
                               f"consumer payload")
        tshape = tuple(payloads[0].shape)
        tdt = np.dtype(payloads[0].dtype)
        for b, p in payloads.items():
            if tuple(p.shape) != tshape or np.dtype(p.dtype) != tdt:
                raise RuntimeError("ragged boundary tile shapes")
        for j in bnd_flows:
            if shapes[j] != tshape or dtypes[j] != tdt:
                raise RuntimeError(
                    f"boundary-fed flow {info.flow_names[j]!r} shape "
                    f"{shapes[j]} != boundary tile {tshape}")

    mesh = xrank_mesh(lane_devs)
    batch = PartitionSpec("xr")
    sh_g = NamedSharding(mesh, batch)
    pos_of = {r: p for p, r in enumerate(wave.ranks)}

    gargs = []
    for j in range(nargs):
        shards = []
        for p, r in enumerate(wave.ranks):
            dev = lane_devs[p]
            rows = [jax.device_put(a, dev)
                    for a in deposits[r]["blocks"][j]]
            if len(rows) < n_max:   # ragged rank: zero-padded rows
                pad = jax.device_put(
                    np.zeros(shapes[j], dtypes[j]), dev)
                rows.extend([pad] * (n_max - len(rows)))
            shards.append(jax.device_put(jnp.stack(rows), dev))
        gargs.append(jax.make_array_from_single_device_arrays(
            (R * n_max,) + shapes[j], sh_g, shards))
    if B:
        bshards = []
        for p, r in enumerate(wave.ranks):
            dev = lane_devs[p]
            rows = []
            for b, (pr, _pk, _fl) in enumerate(wave.boundary):
                if pr == r:
                    # producer-position row: the REAL payload — the
                    # all_gather moves it lane-to-lane in-program
                    rows.append(jax.device_put(payloads[b], dev))
                else:
                    rows.append(jax.device_put(
                        np.zeros(tshape, tdt), dev))   # never read
            bshards.append(jax.device_put(jnp.stack(rows)[None], dev))
        gargs.append(jax.make_array_from_single_device_arrays(
            (R, B) + tshape, sh_g, bshards))
        bidx = np.full((R * n_max, nargs), -1, np.int32)
        for p, r in enumerate(wave.ranks):
            for i, pairs in enumerate(wave.feeds[r]):
                for (j, b) in pairs:
                    bidx[p * n_max + i, j] = \
                        pos_of[wave.boundary[b][0]] * B + b
        ishards = [jax.device_put(bidx[p * n_max:(p + 1) * n_max],
                                  lane_devs[p])
                   for p in range(R)]
        gargs.append(jax.make_array_from_single_device_arrays(
            (R * n_max, nargs), sh_g, ishards))
    if info.local_names:
        L = len(info.local_names)
        loc = np.zeros((R * n_max, L), np.int32)
        for p, r in enumerate(wave.ranks):
            lv = deposits[r]["locals"]
            if lv is not None and len(lv):
                loc[p * n_max:p * n_max + len(lv)] = lv
        lshards = [jax.device_put(loc[p * n_max:(p + 1) * n_max],
                                  lane_devs[p])
                   for p in range(R)]
        gargs.append(jax.make_array_from_single_device_arrays(
            (R * n_max, L), sh_g, lshards))

    key = ("xrank", wave.class_name, wave.ranks, n_max, B, bnd_flows,
           tuple(shapes), tuple(str(d) for d in dtypes), tshape,
           str(tdt), info.local_names,
           tuple(str(d) for d in lane_devs))

    def build():
        t0 = time.perf_counter_ns()
        fn_x = build_xrank_callable(mesh, info, n_max, R, B, bnd_flows,
                                    shapes, dtypes, tshape, tdt)
        compiler.stats["xstage_compiles"] += 1
        compiler.stats["stage_compile_ns"] += \
            time.perf_counter_ns() - t0
        return fn_x

    fn = cached_stage_callable(compiler._token, key, build)
    outs = fn(*gargs)
    tile_nbytes = int(np.prod(tshape, dtype=np.int64)) * tdt.itemsize \
        if B else 0
    return {"outs": outs, "lane_devs": lane_devs, "n_max": n_max,
            "collective_bytes": (R - 1) * B * tile_nbytes}


def build_xrank_callable(mesh, info, n_max: int, R: int, B: int,
                         bnd_flows: Tuple[int, ...], shapes, dtypes,
                         tshape, tdt):
    """ONE shard_map program over the cross-rank lane mesh: every rank
    position unrolls its n_max member rows (the build_wavefront_callable
    template), the boundary stack all_gathers over the rank axis, and
    a traced index routes each boundary-fed flow to its gathered row
    — uniform traced code across shards, so per-rank feed differences
    live in DATA, not in the trace."""
    import jax
    import jax.numpy as jnp

    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.mesh import shard_map_compat

    nargs = info.nargs
    code, rep_env, flow_names = info.code, info.rep_env, info.flow_names
    local_names = info.local_names
    bnd_set = frozenset(bnd_flows)
    batch = PartitionSpec("xr")
    n_in = nargs + (2 if B else 0) + (1 if local_names else 0)

    def local_fn(*blocks):
        off = nargs
        g_flat = bidx_blk = None
        if B:
            bstack = blocks[off][0]          # (B, *tshape) my shard
            off += 1
            g = jax.lax.all_gather(bstack, "xr")   # (R, B, *tshape)
            g_flat = g.reshape((R * B,) + tshape)
            bidx_blk = blocks[off]           # (n_max, nargs) int32
            off += 1
        loc_blk = blocks[off] if local_names else None
        rows = []
        for r in range(n_max):
            env = dict(rep_env)
            for j, fname in enumerate(flow_names):
                v = blocks[j][r]
                if B and j in bnd_set:
                    # sel < 0: locally-fed row — keep the member block
                    sel = bidx_blk[r, j]
                    gathered = g_flat[jnp.maximum(sel, 0)]
                    v = jnp.where(sel >= 0, gathered, v)
                env[fname] = v
            for li, nm in enumerate(local_names):
                env[nm] = loc_blk[r, li]
            env["np"] = np
            env["jnp"] = jnp
            env["es_rank"] = -1   # plan_xwaves rejects bodies reading it
            env["this_task"] = None
            exec(code, env)
            rows.append(tuple(env.get(fname) for fname in flow_names))
        return tuple(jnp.stack([rows[r][o] for r in range(n_max)])
                     for o in range(nargs))

    sharded = shard_map_compat(local_fn, mesh,
                            in_specs=(batch,) * n_in,
                            out_specs=(batch,) * nargs)
    sh = NamedSharding(mesh, batch)
    fn = jax.jit(sharded, in_shardings=(sh,) * n_in,
                 out_shardings=(sh,) * nargs)
    avals = [jax.ShapeDtypeStruct((R * n_max,) + shapes[j], dtypes[j])
             for j in range(nargs)]
    if B:
        avals.append(jax.ShapeDtypeStruct((R, B) + tshape, tdt))
        avals.append(jax.ShapeDtypeStruct((R * n_max, nargs), np.int32))
    if local_names:
        avals.append(jax.ShapeDtypeStruct(
            (R * n_max, len(local_names)), np.int32))
    # force the lower NOW: build failures must downgrade before any
    # peer-visible dispatch, not poison the rendezvous mid-run
    fn.lower(*avals)
    return fn


def _extract(compiler, wave: XWave, info, result: Dict, me: int):
    """Slice my member rows back out of the global outputs and map
    them through MY layout's out_mem/edge maps."""
    lane_devs = result["lane_devs"]
    n_max = result["n_max"]
    my_pos = {r: p for p, r in enumerate(wave.ranks)}[me]
    pos = {d: p for p, d in enumerate(lane_devs)}
    shards = [sorted(o.addressable_shards,
                     key=lambda s: pos[s.device])
              for o in result["outs"]]

    def row(i: int, o: int):
        return shards[o][my_pos].data[i]

    tile_outs = [row(i, o) for (i, o) in info.out_mem_map]
    edge_outs = [row(i, o) for (i, o) in info.edge_map]
    compiler.stats["xstage_collective_bytes"] += \
        result["collective_bytes"]
    return tile_outs, edge_outs
