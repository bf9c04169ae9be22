"""Runtime integration: execute compiled stages as single chores
interleaved with the interpreted residue (ISSUE 12 tentpole, part 4).

A :class:`StageCompiler` attaches to a ``PTGTaskpool`` at startup when
the ``stage_compile`` MCA knob is on.  Each compilable stage becomes
ONE synthetic task on the ordinary runtime: its flows are the stage's
packed buffer slots, its chore is the fused jitted callable (or the
shard_map-compiled wave-front variant on a mesh device), and it rides
the untouched scheduler / device-module / eager-completion machinery —
stage-in, HBM accounting, donation guards, priority stamping and the
PR 7 eager-release window all apply to a stage exactly as they do to a
single task, which is what lets a compiled stage's cross-rank sends
overlap its own execution.

Dynamic dependency tracking for stages piggybacks on the existing
activation protocol: ``PTGTaskClass.activate`` consults the compiler
first (``on_activate``), so activations from local residue tasks,
other stages, AND remote ranks all count toward a stage's external
goal without any wire-format change; when the counter hits zero the
stage task spawns (its fused callable AOT-validated right there) and
is scheduled like any ready task.  On completion the stage's release
walk reuses each member's untouched ``_release_deps`` — remote
activations batch per rank, memory writebacks ride the device epilog —
with intra-stage edges swallowed by the same ``on_activate`` seam.

ISSUE 13 grew three fronts onto this engine, all behind the same knob:

- **Cross-pool chaining** (stagec/chain.py): when the context carries
  a declared chain, the host pool's final stage lowers into the
  CHAINED program (host stage + rider stages of later pools) and the
  rider pools CONSUME their pre-computed first-stage outputs at
  startup (``consume_chain``) — zero dispatch, tiles stay
  device-resident.  A chained build failure falls back to the plain
  host-only callable (``CHAIN_FALLBACKS``), and a rider whose stash
  never filled spawns its stage normally.
- **Compiled residue schedule**: residue tasks in a pre-planned
  per-(level, class) group (``plan.residue_groups``) are BUFFERED as
  they become ready and handed to the device batching pipeline as one
  contiguous burst when the group completes — no per-task scheduler
  round-trip, and the burst is guaranteed to flush as stacked calls.
- **Prestage/execute overlap**: buffered activation payloads H2D-stage
  at ARRIVAL (while the producing stage still executes or the wire
  still delivers), a spawning stage's own host-resident tiles stage
  under its trace/compile, and completed stages prestage the next
  pending stages' final-valued tiles — all through the §6.1 set
  stage-in's device seam (``JaxDevice.prestage_many``), bounded by
  ``device_prefetch_depth``, counted in ``PRESTAGE_ISSUED``/
  ``PRESTAGE_HITS`` and visible to the live overlap gauge.

Fallback ladder (semantics are never at risk):

1. a class the lowerability pass rejects stays interpreted (residue);
2. a stage whose fused trace fails at spawn DOWNGRADES — its buffered
   activations replay through the normal dynamic path and its members
   execute via the PR 5/7 batched dispatch, permanently but only for
   that stage (the failure is cached, other stages keep compiling);
3. a chained program that fails to lower falls back to the host-only
   fused callable (riders spawn normally from their own pools);
4. a sharded (mesh) build/dispatch failure falls back to the fused
   single-chip callable for that stage;
5. ``stage_compile`` unset: ``tp._stagec`` is None and behavior is
   bit-for-bit the pre-stagec runtime.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..data.data import Coherency, Data, DataCopy, FlowAccess
from ..obs.spans import inbound_flow_ctx
from ..runtime.taskpool import (ACTION_RELEASE_ALL, Chore, Flow, Task,
                                TaskClass)
from ..utils import logging as plog
from ..utils.params import params
from .lower import (StageLayout, build_layout, build_stage_fn,
                    spec_token, stage_signature)
from .plan import StagePlan, plan_stages

#: declared lock discipline (analysis/lock_check.py): a stage record's
#: dependency counter, buffered activation events, and lifecycle status
#: are mutated from worker threads AND the comm delivery path — every
#: access goes through the record's own lock.  ``edge_copies`` is
#: single-owner by lifecycle (written by the dispatching manager, read
#: by the completing worker's release walk, ordered by the task
#: lifecycle) and deliberately unregistered.
_GUARDED_BY = {
    "_StageRec.remaining": "_lock",
    "_StageRec.events": "_lock",
    "_StageRec.status": "_lock",
    "_StageRec.flow_ctxs": "_lock",
    "StageCompiler._rg_left": "_rg_lock",
    "StageCompiler._rg_buf": "_rg_lock",
}

# _StageRec lifecycle
_PENDING, _SPAWNED, _DONE, _DOWNGRADED = range(4)

#: cache sentinel: a stage signature whose build already failed —
#: the next taskpool over the same spec downgrades instantly instead
#: of re-tracing the known failure ("permanent, but only for that
#: stage")
_FAILED = object()

#: consume_chain sentinel: no stash entry AT ALL (the host program
#: never ran) — distinct from a None marker (the host fell back and
#: already counted the fallback)
_NO_STASH = object()


class _StageRec:
    """One stage's dynamic state on one taskpool."""

    def __init__(self, stage, layout: StageLayout, priority: int) -> None:
        self.stage = stage
        self.layout = layout
        self.priority = priority
        self._lock = threading.Lock()
        self.remaining = layout.goal
        self.events: List[Tuple] = []   # (member_key, flow, copy) buffered
        # wire trace contexts of the remote activations that fed this
        # stage (ISSUE 15; only collected while a profile is live) —
        # stamped onto the stage task's exec span so the merged
        # timeline can attribute the fused span to its cross-rank
        # inputs
        self.flow_ctxs: List[Tuple] = []
        self.status = _PENDING
        self.fn = None                  # fused jitted callable
        self.sharded = None             # (fn, sharding, info) or None
        self.task: Optional[Task] = None
        self.edge_copies: Dict[Tuple, Any] = {}
        self.shapes: Tuple = ()
        self.donate: Tuple = ()
        self.chain = None               # HostChain when this rec hosts
        self.xwave = None               # XWave when this rec joins one
        #: Data objects prestaged for this stage and not yet counted
        #: (single-owner by lifecycle: the buffering/spawn path)
        self.prestaged: List[Any] = []


class StageTaskClass(TaskClass):
    """The synthetic task class of ONE compiled stage: flows are the
    stage's packed buffer slots.  Never registered on the taskpool's
    ``task_classes`` (remote activation ids index that list), so the
    wire protocol is untouched."""

    def __init__(self, compiler: "StageCompiler", rec: _StageRec) -> None:
        lay = rec.layout
        flows: List[Flow] = []
        for i, ((coll, coords), access) in enumerate(lay.mem_slots):
            flows.append(Flow(f"{coll}{coords}", access, i))
        base = len(lay.mem_slots)
        for j, (mkey, fname) in enumerate(lay.act_slots):
            flows.append(Flow(f"{mkey[0]}{mkey[1]}.{fname}",
                              FlowAccess.READ, base + j))
        if rec.chain is not None:
            # chained host stage (ISSUE 13): the riders' extra tiles
            # join the packed buffer as READ flows after the act slots
            base = len(flows)
            for j, (coll, coords) in enumerate(rec.chain.extra):
                flows.append(Flow(
                    f"chain:{getattr(coll, 'name', 'tile')}{coords}",
                    FlowAccess.READ, base + j))
        super().__init__(f"STAGE{rec.stage.index}[{compiler.tp.name}]",
                         -1 - rec.stage.index, len(flows), flows=flows)
        from ..devices.tpu import tpu_chore_hook
        self.incarnations = [Chore("tpu", tpu_chore_hook(),
                                   dyld_fn=compiler._make_dyld(rec))]
        self.release_deps = \
            lambda es, task, mask, c=compiler, r=rec: c._release(es, r)
        # one stage completion retires every member task's count (the
        # final unit comes from complete_execution's own decrement)
        n = rec.stage.n_tasks
        if n > 1:
            self.complete_execution = \
                lambda es, task, tp=compiler.tp: tp.task_completed(n - 1)


class StageCompiler:
    """Per-taskpool stage-compile engine (``tp._stagec``)."""

    def __init__(self, tp, context, plan: StagePlan) -> None:
        self.tp = tp
        self.context = context
        self.plan = plan
        self.stats = context.stage_stats
        # span-context collection (ISSUE 15) only while a profile is
        # live: the activate redirect is a hot path
        self._trace_on = context.profile is not None
        from .lower import spec_codes
        self._codes = spec_codes(tp)
        self._token = spec_token(tp)
        self._donate_on = bool(params.get("device_donate"))
        # donate-by-default (ISSUE 20c): donation is ON inside compiled
        # stages without the device_donate opt-in, EXCEPT for stages
        # whose member classes carry a BDY204 verdict (two flows read
        # the same tile — donating would hand XLA a buffer another
        # flow still needs)
        self._donate_default = bool(params.get("stage_compile_donate"))
        self._bdy_aliased: set = set()
        if self._donate_default:
            try:
                from ..analysis.body_check import check_jdf_bodies
                from .plan import _finding_class
                self._bdy_aliased = {
                    _finding_class(f) for f in check_jdf_bodies(tp.jdf)
                    if f.code == "BDY204"}
            except Exception:  # noqa: BLE001 - analysis is advisory
                self._donate_default = False
        # the mesh device, when this rank's accelerator is one (PR 6):
        # wave-front stages then compile through shard_map over it
        self._mesh_dev = next(
            (d for d in context.devices
             if d.device_type == "tpu" and getattr(d, "mesh", None)
             is not None and len(getattr(d, "chips", ())) > 1), None)
        self._dev = next(d for d in context.devices
                         if d.device_type == "tpu")
        self._recs: List[_StageRec] = []
        self._member_rec: Dict[Tuple, _StageRec] = {}
        self._rec_by_index: Dict[int, _StageRec] = {}
        for stage, layout, prio in plan.prepared:
            rec = _StageRec(stage, layout, prio)
            self._recs.append(rec)
            self._rec_by_index[stage.index] = rec
            for m in stage.members:
                self._member_rec[m.key] = rec

        # cross-pool chaining (ISSUE 13, stagec/chain.py): does this
        # pool HOST a chained program, or CONSUME a stash?  A rider may
        # contribute a multi-stage prefix (ISSUE 20a): one rec per
        # fused link, in stage order, all-or-nothing
        self._consume_recs: List[_StageRec] = []
        chain_state = getattr(context, "_stage_chain", None)
        if chain_state is not None:
            # pop: the HostChain moves onto the rec, so the registry
            # entry (and eventually the pool's strong ref) can retire
            hc = chain_state.hosts.pop(id(tp), None)
            if hc is not None:
                host_rec = self._rec_by_index.get(hc.host_stage_index)
                if host_rec is not None:
                    host_rec.chain = hc
            links = chain_state.consumes.get(id(tp))
            if links:
                recs = []
                for link in links:
                    rec0 = self._rec_by_index.get(link.stage.index)
                    if rec0 is None or rec0.stage is not link.stage:
                        recs = []
                        break
                    recs.append(rec0)
                self._consume_recs = recs

        # compiled residue schedule (ISSUE 13): per-(level, class)
        # groups pre-planned by the lowerability pass — ready members
        # buffer here and dispatch as ONE device burst when complete
        self._rg_lock = threading.Lock()
        self._rg_of: Dict[Tuple, int] = {}
        self._rg_left: List[int] = []
        self._rg_buf: List[List[Task]] = []
        self._rg_host: List[bool] = []
        if params.get("stage_residue_batch") and \
                (plan.residue_groups or plan.residue_groups_host):
            eligible = {
                tc.ast.name for tc in tp.task_classes
                if any(c.device_type == "tpu" and c.dyld_fn is not None
                       for c in tc.incarnations)}
            host_ok = {
                tc.ast.name for tc in tp.task_classes
                if any(c.device_type == "cpu" for c in tc.incarnations)}
            for host, groups in ((False, plan.residue_groups),
                                 (True, plan.residue_groups_host)):
                for keys in groups:
                    if keys[0][0] not in (host_ok if host else eligible):
                        continue
                    gi = len(self._rg_left)
                    self._rg_left.append(len(keys))
                    self._rg_buf.append([])
                    self._rg_host.append(host)
                    for k in keys:
                        self._rg_of[k] = gi

        # prestage/execute overlap (ISSUE 13): early H2D of stage
        # inputs through the §6.1 set stage-in's device seam, bounded by
        # device_prefetch_depth stages with outstanding prestages
        self._prestage_depth = int(getattr(self._dev, "prefetch_depth",
                                           0))
        self._prestage_recs: set = set()

        # cross-rank SPMD stages (ISSUE 20): negotiate "xs" with every
        # spanning peer, exchange + assert the plan digest, wire the
        # planned waves onto their stage recs.  Any soft failure keeps
        # every stage rank-local; a DIGEST mismatch raises (ranks
        # disagreeing on the wave partition is a plan bug, the
        # xfer/plan.py loud-failure contract).
        self._xrank = None
        if getattr(plan, "xwaves", None):
            from .xrank import install_xrank
            try:
                install_xrank(self)
            except RuntimeError:
                raise
            except Exception as exc:  # noqa: BLE001 - rank-local stands by
                plog.warning(
                    "stagec xrank: install failed on %s (%s: %s); "
                    "rank-local stages", tp.name, type(exc).__name__,
                    str(exc)[:200])
                self._xrank = None
                for r_ in self._recs:
                    r_.xwave = None

    def _tc(self, inst):
        """The LIVE taskpool's class for a (possibly cached-plan)
        instance: plans are cached per spec token across taskpools, so
        ``inst.tc`` may belong to an earlier pool — every runtime
        action rebinds by name."""
        return self.tp.class_by_name(inst.tc.ast.name)

    # ------------------------------------------------------------------ #
    # dependency tracking: the activate redirect                         #
    # ------------------------------------------------------------------ #
    def on_activate(self, tc, locals_: Tuple, flow_name: str,
                    copy) -> Tuple[bool, Optional[Task]]:
        """Called by ``PTGTaskClass.activate`` before its own dynamic
        dep table.  Returns ``(handled, ready_task)``; handled=False
        passes through to the interpreted path (non-members and
        downgraded stages)."""
        rec = self._member_rec.get((tc.ast.name, locals_))
        if rec is None:
            return False, None
        spawn = False
        with rec._lock:
            if rec.status == _DOWNGRADED:
                return False, None
            if rec.status != _PENDING:
                # an intra-stage edge emitted by the release walk of
                # this very stage: already computed inside the fused
                # program — swallow
                return True, None
            rec.events.append(((tc.ast.name, locals_), flow_name, copy))
            if self._trace_on:
                # which wire flow (if any) delivered this activation:
                # remote_dep publishes the inbound context thread-
                # locally around the activation walk (obs/spans.py)
                fctx = inbound_flow_ctx()
                if fctx is not None:
                    rec.flow_ctxs.append(fctx)
            rec.remaining -= 1
            assert rec.remaining >= 0, \
                f"{tc.ast.name}{locals_}: stage overshoot"
            if rec.remaining == 0:
                rec.status = _SPAWNED   # claim; build outside the lock
                spawn = True
        if not spawn:
            # prestage the buffered payload NOW (ISSUE 13): the stage
            # still awaits other inputs, so its H2D overlaps whatever
            # is producing them (the executing stage / the wire)
            self._prestage_activation(rec, copy)
            return True, None
        tasks = self._spawn(rec)
        if not tasks:
            return True, None
        if len(tasks) > 1:
            from ..runtime.scheduling import schedule
            schedule(self.context.execution_streams[0], tasks[1:])
        return True, tasks[0]

    def startup_tasks(self) -> List[Task]:
        """Stages with no external task inputs are startup tasks.  A
        stage another pool's chained program pre-computes stays PENDING
        here — ``consume_chain`` finalizes (or falls back) after the
        taskpool's counts are credited."""
        out: List[Task] = []
        for rec in self._recs:
            if rec in self._consume_recs:
                continue
            with rec._lock:
                if rec.status != _PENDING or rec.remaining > 0:
                    continue
                rec.status = _SPAWNED
            out.extend(self._spawn(rec))
        return out

    def is_member(self, class_name: str, locals_: Tuple) -> bool:
        rec = self._member_rec.get((class_name, locals_))
        if rec is None:
            return False
        with rec._lock:
            return rec.status != _DOWNGRADED

    # ------------------------------------------------------------------ #
    # cross-pool chaining: consume a stashed rider stage (ISSUE 13)      #
    # ------------------------------------------------------------------ #
    def consume_chain(self, es) -> List[Task]:
        """Finalize this pool's chained-in first stage: adopt the
        stashed device outputs as the newest tile copies, run the
        stage's release walk, retire its members' counts.  Called by
        ``PTGTaskpool._startup`` AFTER the task counts are credited (a
        completion before ``set_nb_tasks`` would go negative).  A
        missing stash (the host program downgraded, or never ran)
        falls back to spawning the stage normally."""
        recs = self._consume_recs
        if not recs:
            return []
        self._consume_recs = []
        st = getattr(self.context, "_stage_chain", None)
        stash = st.stash.pop(id(self.tp), _NO_STASH) if st is not None \
            else _NO_STASH
        if st is not None:
            st.consumes.pop(id(self.tp), None)
        if not isinstance(stash, list) and stash is not None \
                and stash is not _NO_STASH:
            stash = [stash]
        if isinstance(stash, list) and len(stash) != len(recs):
            # the host lowered a different prefix than this pool fused
            # (stale registry entry): dispatch everything normally
            stash = _NO_STASH
        if stash is None or stash is _NO_STASH:
            if stash is _NO_STASH:
                # the host program never ran at all (downgrade, knob
                # change); a None marker means the host already fell
                # back — and already counted the fallback
                self.stats["chain_fallbacks"] += 1
            plog.debug.verbose(
                2, "stagec chain: %s found no stash for stage %d; "
                "dispatching it normally", self.tp.name,
                recs[0].stage.index)
            out: List[Task] = []
            for rec in recs:
                # later prefix recs with remaining > 0 stay PENDING and
                # spawn through the ordinary activation path
                with rec._lock:
                    if rec.status != _PENDING or rec.remaining > 0:
                        continue
                    rec.status = _SPAWNED
                out.extend(self._spawn(rec))
            return out
        # mark EVERY fused rec spawned up front: an earlier rec's
        # release walk must not re-dispatch a later fused rec through
        # the activation path (its activations are in-program)
        for rec in recs:
            with rec._lock:
                rec.status = _SPAWNED
        ready: List[Task] = []
        total = 0
        for rec, part in zip(recs, stash):
            lay = rec.layout
            for arr, si in zip(part["tiles"], lay.out_mem):
                (coll_name, coords), _a = lay.mem_slots[si]
                data = self.tp.global_env[coll_name].data_of(*coords)
                self._dev.adopt_output(data, arr)
            for ek, arr in zip(lay.edge_outs, part["edges"]):
                if arr is not None:
                    rec.edge_copies[ek] = _edge_copy(arr)
            n = rec.stage.n_tasks
            self.stats["chain_links"] += 1
            self.stats["stage_tasks"] += n
            self._dev.stats["tasks"] += n
            ready.extend(self._release(es, rec))
            self.tp.task_completed(n)
            total += n
        plog.debug.verbose(
            3, "stagec chain: %s consumed %d stage(s) (%d task(s)) "
            "from the chained program", self.tp.name, len(recs), total)
        return ready

    # ------------------------------------------------------------------ #
    # compiled residue schedule (ISSUE 13)                               #
    # ------------------------------------------------------------------ #
    def on_residue_ready(self, task: Task) -> Optional[Task]:
        """A residue task just became ready (``PTGTaskClass.activate``
        routes every non-member spawn here).  Members of a pre-planned
        residue group BUFFER; the completed group is handed to the
        device batching pipeline as one contiguous burst — no per-task
        scheduler round-trip, and the burst flushes as stacked calls.
        Non-grouped tasks pass through untouched."""
        gi = self._rg_of.get((task.task_class.ast.name, task.locals))
        if gi is None:
            return task
        with self._rg_lock:
            self._rg_buf[gi].append(task)
            self._rg_left[gi] -= 1
            if self._rg_left[gi] > 0:
                return None
            group, self._rg_buf[gi] = self._rg_buf[gi], []
        if self._rg_host[gi]:
            self._dispatch_host_group(group)
        else:
            self._dispatch_residue_group(group)
        return None

    def _dispatch_residue_group(self, tasks: List[Task]) -> None:
        """Hand one complete residue group straight to the device:
        inputs bound (prepare_input), device chore selected, every
        task pushed onto the device queue back to back — the next
        manager flush drains them as ONE accumulated burst through the
        PR 5 stacked dispatch.  No scheduler enqueue/select per task."""
        es0 = self.context.execution_streams[0]
        dev = self._dev
        self.stats["residue_batches"] += 1
        self.stats["residue_batch_tasks"] += len(tasks)
        for task in tasks:
            tc = task.task_class
            if tc.prepare_input is not None:
                tc.prepare_input(es0, task)
            task.selected_chore = next(
                i for i, c in enumerate(tc.incarnations)
                if c.device_type == "tpu")
            task.selected_device = dev
            est = (tc.time_estimate(task, dev) if tc.time_estimate
                   else dev.time_estimate_default)
            dev.load_add(est)
            task.es_hint = es0.th_id
            dev.pending.push_back((task, est))
        # no inline progress: the next idle worker's manager cycle
        # drains the whole burst with ITS execution stream
        self.context.wake_workers(len(tasks))

    def _dispatch_host_group(self, tasks: List[Task]) -> None:
        """Host-bodied counterpart (ISSUE 20b): a complete pre-planned
        group of HOST residue tasks enters the scheduler as ONE
        contiguous burst — same-(level, class) members are an
        antichain, so nothing in the group depends on anything else in
        it and the whole batch is ready at once."""
        es0 = self.context.execution_streams[0]
        self.stats["residue_batches"] += 1
        self.stats["residue_batch_tasks"] += len(tasks)
        from ..runtime.scheduling import schedule
        schedule(es0, tasks)
        self.context.wake_workers(len(tasks))

    # ------------------------------------------------------------------ #
    # prestage/execute overlap (ISSUE 13)                                #
    # ------------------------------------------------------------------ #
    def _prestage_activation(self, rec: _StageRec, copy) -> None:
        """Early H2D of a buffered activation payload: the stage still
        awaits other inputs, so this transfer hides under whatever is
        producing them.  Budgeted: at most ``device_prefetch_depth``
        pending stages hold outstanding prestages at once."""
        if self._prestage_depth <= 0 or copy is None \
                or copy.data is None:
            return
        if id(rec) not in self._prestage_recs \
                and len(self._prestage_recs) >= self._prestage_depth:
            return
        if self._dev.prestage_data(copy.data):
            self._prestage_recs.add(id(rec))
            rec.prestaged.append(copy.data)
            self.stats["prestage_issued"] += 1

    def _prestage_own_tiles(self, rec: _StageRec) -> None:
        """H2D the spawning stage's host-resident tiles NOW, so the
        transfers run under the stage's trace/compile below instead of
        serializing ahead of its dispatch.  Safe: the stage's
        activation goal is met, so every tile it reads holds its final
        value (memory ordering between tasks is dataflow-carried)."""
        if self._prestage_depth <= 0:
            return
        tiles = [self.tp.global_env[name].data_of(*coords)
                 for (name, coords), _a in rec.layout.mem_slots]
        if rec.chain is not None:
            tiles.extend(coll.data_of(*coords)
                         for coll, coords in rec.chain.extra)
        committed = self._dev.prestage_many(tiles)
        if committed:
            rec.prestaged.extend(committed)
            self.stats["prestage_issued"] += len(committed)

    def _prestage_lookahead(self) -> None:
        """A stage just completed: prestage the next PENDING stages'
        tiles whose writers are all retired (their host values are
        final), up to the device_prefetch_depth stage budget — stage
        N+1's packed-buffer stage-in overlaps what still executes."""
        if self._prestage_depth <= 0:
            return
        budget = self._prestage_depth
        writers = self.plan.mem_writers
        member_stage = self.plan.member_stage
        for rec in self._recs:
            if budget <= 0:
                break
            with rec._lock:
                if rec.status != _PENDING:
                    continue
            budget -= 1
            for (coll_name, coords), _access in rec.layout.mem_slots:
                final = True
                for wk in writers.get((coll_name, coords), ()):
                    wsi = member_stage.get(wk)
                    wrec = (self._rec_by_index.get(wsi)
                            if wsi is not None else None)
                    if wrec is None:
                        final = False   # residue or foreign writer
                        break
                    with wrec._lock:
                        if wrec.status != _DONE:
                            final = False   # value not yet final
                    if not final:
                        break
                if not final:
                    continue
                data = self.tp.global_env[coll_name].data_of(*coords)
                if self._dev.prestage_data(data):
                    self._prestage_recs.add(id(rec))
                    rec.prestaged.append(data)
                    self.stats["prestage_issued"] += 1

    def _count_prestage_hits(self, rec: _StageRec) -> None:
        """At spawn: every prestaged Data whose device copy is still
        current is a HIT — the fused stage's stage-in finds the buffer
        resident instead of paying a serial H2D."""
        for data in rec.prestaged:
            if self._dev.prestaged_current(data):
                self.stats["prestage_hits"] += 1
        rec.prestaged = []
        self._prestage_recs.discard(id(rec))

    # ------------------------------------------------------------------ #
    # spawn: AOT-validate the fused callable, bind slots, emit the task  #
    # ------------------------------------------------------------------ #
    def _spawn(self, rec: _StageRec) -> List[Task]:
        try:
            return [self._make_stage_task(rec)]
        except Exception as exc:  # noqa: BLE001 - any failure interprets
            plog.warning(
                "stagec: stage %d of %s failed to lower (%s: %s); its %d "
                "member task(s) run interpreted",
                rec.stage.index, self.tp.name, type(exc).__name__,
                str(exc)[:200], rec.stage.n_tasks)
            return self._downgrade(rec)

    def _slot_shapes(self, rec: _StageRec, bindings: Dict) -> Tuple:
        shapes = []
        for (coll_name, coords), _access in rec.layout.mem_slots:
            coll = self.tp.global_env[coll_name]
            data = coll.data_of(*coords)
            newest = data.newest_copy()
            if newest is not None and newest.payload is not None:
                shapes.append((tuple(newest.payload.shape),
                               str(newest.payload.dtype)))
            else:
                shapes.append((tuple(coll.tile_shape(*coords)),
                               str(np.dtype(coll.dtype))))
        for ak in rec.layout.act_slots:
            cp = bindings.get(ak)
            if cp is None or cp.payload is None:
                raise RuntimeError(
                    f"activation slot {ak} bound no payload")
            shapes.append((tuple(cp.payload.shape),
                           str(cp.payload.dtype)))
        return tuple(shapes)

    def _lowered(self, rec: _StageRec, donate: Tuple) -> Any:
        """The AOT-cached fused callable for this stage signature —
        alongside the bucket cache (devices/batching.py); a repeat
        taskpool over the same spec/NB/dtype hits it without
        re-tracing.  A cached failure re-raises instantly."""
        import jax
        from ..devices.batching import cached_stage_callable

        key = stage_signature(rec.stage, rec.shapes) + (donate, "fused")

        def build():
            t0 = time.perf_counter_ns()
            run = build_stage_fn(self.tp, rec.stage, rec.layout,
                                 self._codes)
            fn = jax.jit(run, donate_argnums=donate)
            # force the trace NOW: untraceable bodies must downgrade at
            # spawn, not poison the device dispatch path
            avals = tuple(jax.ShapeDtypeStruct(s, np.dtype(d))
                          for (s, d) in rec.shapes)
            jax.eval_shape(run, *avals)
            dt = time.perf_counter_ns() - t0
            self.stats["stage_compiles"] += 1
            self.stats["stage_compile_ns"] += dt
            return fn

        fn = cached_stage_callable(self._token, key, build)
        if fn is _FAILED:
            raise RuntimeError("stage lowering previously failed "
                               "(cached verdict)")
        return fn

    def _extra_shapes(self, rec: _StageRec) -> Tuple:
        shapes = []
        for coll, coords in rec.chain.extra:
            data = coll.data_of(*coords)
            newest = data.newest_copy()
            if newest is not None and newest.payload is not None:
                shapes.append((tuple(newest.payload.shape),
                               str(newest.payload.dtype)))
            else:
                shapes.append((tuple(coll.tile_shape(*coords)),
                               str(np.dtype(coll.dtype))))
        return tuple(shapes)

    def _lowered_chain(self, rec: _StageRec, donate: Tuple) -> Any:
        """The AOT-cached CHAINED program of a host stage (stagec/
        chain.py): host stage + rider stages of later pools, cached
        under the host pool's spec token.  A cached failure re-raises
        instantly (the caller falls back to the host-only callable)."""
        import jax
        from ..devices.batching import cached_stage_callable
        from .chain import build_chain_run, chain_signature

        key = chain_signature(rec.shapes, rec.stage, rec.chain, donate)

        def build():
            t0 = time.perf_counter_ns()
            try:
                run = build_chain_run(self.tp, rec.stage, rec.layout,
                                      self._codes, rec.chain)
                fn = jax.jit(run, donate_argnums=donate)
                avals = tuple(jax.ShapeDtypeStruct(s, np.dtype(d))
                              for (s, d) in rec.shapes)
                jax.eval_shape(run, *avals)
            except Exception:
                cached_stage_callable(self._token, key, lambda: _FAILED)
                raise
            self.stats["stage_compiles"] += 1
            self.stats["stage_compile_ns"] += \
                time.perf_counter_ns() - t0
            return fn

        fn = cached_stage_callable(self._token, key, build)
        if fn is _FAILED:
            raise RuntimeError("chained lowering previously failed "
                               "(cached verdict)")
        return fn

    def _make_stage_task(self, rec: _StageRec) -> Task:
        with rec._lock:
            events = list(rec.events)
        bindings: Dict[Tuple, Any] = {}
        for (mkey, fname, copy) in events:
            if copy is not None:
                bindings[(mkey, fname)] = copy
        # prestage the stage's host-resident tiles: their H2D runs
        # under the trace/compile below (ISSUE 13 overlap)
        self._prestage_own_tiles(rec)
        rec.shapes = self._slot_shapes(rec, bindings)
        if rec.chain is not None:
            rec.shapes = rec.shapes + self._extra_shapes(rec)
        donate_ok = self._donate_on or (
            self._donate_default
            and not any(m.tc.ast.name in self._bdy_aliased
                        for m in rec.stage.members))
        rec.donate = tuple(
            i for i, (_k, acc) in enumerate(rec.layout.mem_slots)
            if donate_ok and (acc & FlowAccess.WRITE))
        from ..devices.batching import cached_stage_callable
        try:
            if rec.chain is not None:
                try:
                    rec.fn = self._lowered_chain(rec, rec.donate)
                except Exception as exc:  # noqa: BLE001 - host stands by
                    self.stats["chain_fallbacks"] += 1
                    plog.warning(
                        "stagec chain: chained program of %s stage %d "
                        "failed to lower (%s: %s); host-only callable "
                        "(riders dispatch from their own pools)",
                        self.tp.name, rec.stage.index,
                        type(exc).__name__, str(exc)[:200])
                    st = getattr(self.context, "_stage_chain", None)
                    if st is not None:
                        # a None stash tells each rider "the host fell
                        # back, spawn normally" — counted HERE once,
                        # not once more per rider
                        for link in rec.chain.riders:
                            st.stash[id(link.tp)] = None
                    rec.chain = None
                    rec.shapes = self._slot_shapes(rec, bindings)
                    rec.fn = self._lowered(rec, rec.donate)
            else:
                rec.fn = self._lowered(rec, rec.donate)
        except Exception:
            # record the verdict so the next taskpool over the same
            # spec downgrades this stage instantly (permanent, but
            # only for this stage)
            cached_stage_callable(
                self._token,
                stage_signature(rec.stage, rec.shapes)
                + (rec.donate, "fused"),
                lambda: _FAILED)
            raise
        if self._mesh_dev is not None and rec.chain is None \
                and params.get("stage_compile_shard"):
            rec.sharded = self._try_sharded(rec)
        self._count_prestage_hits(rec)
        tc = StageTaskClass(self, rec)
        if self._trace_on:
            # stage-task spans carry member contexts (ISSUE 15): the
            # fused exec span lists its member tasks and the wire flow
            # ids that fed it, so the merged timeline can tie one
            # stage slice to its cross-rank inputs
            with rec._lock:
                ctxs = list(rec.flow_ctxs)
            tc.trace_info = {
                "stage_members": rec.stage.n_tasks,
                "member_tasks": [f"{m.key[0]}{tuple(m.key[1])}"
                                 for m in rec.stage.members[:16]],
                "wire_flows": [f"R{o}:{s}" for (o, s) in ctxs[:32]],
            }
        task = Task(self.tp, tc, locals_=(rec.stage.index,),
                    priority=rec.priority)
        task.user = rec
        for i, ((coll_name, coords), _a) in enumerate(rec.layout.mem_slots):
            coll = self.tp.global_env[coll_name]
            task.data[i].data_in = coll.data_of(*coords).host_copy()
            task.data[i].fulfilled = True
        base = len(rec.layout.mem_slots)
        for j, ak in enumerate(rec.layout.act_slots):
            task.data[base + j].data_in = bindings[ak]
            task.data[base + j].fulfilled = True
        if rec.chain is not None:
            base += len(rec.layout.act_slots)
            for j, (coll, coords) in enumerate(rec.chain.extra):
                task.data[base + j].data_in = \
                    coll.data_of(*coords).host_copy()
                task.data[base + j].fulfilled = True
        rec.task = task
        return task

    def _try_sharded(self, rec: _StageRec):
        """Wave-front stages on a mesh rank compile through shard_map
        over the rank's chips (stagec/sharded.py); any failure keeps
        the fused single-chip callable."""
        from .sharded import build_wavefront_callable, wavefront_info
        dev = self._mesh_dev
        k = len(dev.chips)
        n = rec.stage.n_tasks
        if n < k or n % k:
            return None
        try:
            info = wavefront_info(self.tp, rec.stage, rec.layout,
                                  self._codes)
            if info is None:
                return None
            row_shapes = tuple(
                rec.shapes[info.arg_slots[0][j]] for j in range(info.nargs))
            from ..devices.batching import cached_stage_callable
            key = stage_signature(rec.stage, rec.shapes) + \
                ("sharded", dev.mesh)

            def build():
                t0 = time.perf_counter_ns()
                fn_sh = build_wavefront_callable(dev.mesh, info,
                                                 self.tp.rank, row_shapes)
                self.stats["stage_compiles"] += 1
                self.stats["stage_compile_ns"] += \
                    time.perf_counter_ns() - t0
                return fn_sh

            fn, sharding = cached_stage_callable(self._token, key, build)
            return (fn, sharding, info)
        except Exception as exc:  # noqa: BLE001 - fused path stands by
            plog.debug.verbose(
                2, "stagec: sharded lowering of stage %d declined (%s); "
                "fused single-chip callable", rec.stage.index, exc)
            return None

    # ------------------------------------------------------------------ #
    # downgrade: replay into the interpreted dynamic path                #
    # ------------------------------------------------------------------ #
    def _downgrade(self, rec: _StageRec) -> List[Task]:
        """Transparent per-stage fallback: buffered external
        activations replay through the normal per-class dep tables and
        the members execute via the interpreted (batched, PR 5/7)
        dispatch.  Permanent only for this stage — other stages keep
        their compiled path."""
        with rec._lock:
            rec.status = _DOWNGRADED
            events, rec.events = rec.events, []
        if rec.xwave is not None:
            # peers are (or will be) waiting at this wave's rendezvous:
            # decline NOW so they fall back instead of timing out
            from .xrank import decline_rec
            decline_rec(self, rec)
            rec.xwave = None
            self.stats["xstage_fallbacks"] += 1
        rec.prestaged = []
        self._prestage_recs.discard(id(rec))
        self.stats["stage_fallbacks"] += 1
        ready: List[Task] = []
        for inst in rec.stage.members:
            tc = self._tc(inst)
            if tc.goal_of(inst.locals) == 0:
                ready.append(tc.make_task(inst.locals, None))
        for (mkey, fname, copy) in events:
            tc = self.tp.class_by_name(mkey[0])
            t = tc.activate(mkey[1], fname, copy)
            if t is not None:
                ready.append(t)
        return ready

    # ------------------------------------------------------------------ #
    # execution: the stage chore                                         #
    # ------------------------------------------------------------------ #
    def _make_dyld(self, rec: _StageRec):
        def dyld(task: Task, arrays: List[Any]):
            return self._execute_stage(task, rec, arrays)
        return dyld

    def _execute_stage(self, task: Task, rec: _StageRec,
                       arrays: List[Any]):
        lay = rec.layout
        tile_outs = edge_outs = None
        if rec.xwave is not None:
            from .xrank import decline_rec, dispatch_xrank
            try:
                tile_outs, edge_outs = dispatch_xrank(self, rec, arrays)
                self.stats["xstage_tasks"] += rec.stage.n_tasks
            except Exception as exc:  # noqa: BLE001 - rank-local ladder
                plog.warning(
                    "stagec xrank: cross-rank dispatch of stage %d "
                    "failed (%s: %s); rank-local path",
                    rec.stage.index, type(exc).__name__, str(exc)[:200])
                decline_rec(self, rec)
                rec.xwave = None
                self.stats["xstage_fallbacks"] += 1
                tile_outs = None
        if tile_outs is None and rec.sharded is not None:
            from .sharded import dispatch_sharded
            fn, sharding, info = rec.sharded
            try:
                tile_outs, edge_outs = dispatch_sharded(
                    self._mesh_dev, fn, sharding, info, arrays)
                self.stats["stage_sharded"] += 1
            except Exception as exc:  # noqa: BLE001 - fused fallback
                plog.warning(
                    "stagec: sharded dispatch of stage %d failed (%s); "
                    "fused single-chip dispatch", rec.stage.index, exc)
                rec.sharded = None
                tile_outs = None
        if tile_outs is None:
            fn = rec.fn
            if rec.donate and len({id(a) for a in arrays}) != len(arrays):
                # the same buffer at two slots: donation would trip
                # XLA's aliasing rule — use the undonated variant
                fn = (self._lowered_chain(rec, ())
                      if rec.chain is not None else self._lowered(rec, ()))
            outs = fn(*arrays)
            ntile = len(lay.out_mem)
            nhost = ntile + len(lay.edge_outs)
            tile_outs = list(outs[:ntile])
            edge_outs = list(outs[ntile:nhost])
            if rec.chain is not None:
                # stash each rider stage's outputs for its pool's
                # consume_chain (stagec/chain.py): tiles + edge
                # live-outs, still (possibly in-flight) device arrays.
                # A rider pool may own SEVERAL links (multi-stage
                # prefix, ISSUE 20a): its stash is the per-link list
                # in stage order
                st = getattr(self.context, "_stage_chain", None)
                rest = list(outs[nhost:])
                stash_by_tp: Dict[int, List[Dict[str, Any]]] = {}
                for link in rec.chain.riders:
                    nt = len(link.layout.out_mem)
                    part, rest = rest[:link.n_out], rest[link.n_out:]
                    stash_by_tp.setdefault(id(link.tp), []).append(
                        {"tiles": part[:nt], "edges": part[nt:]})
                if st is not None:
                    for tpid, parts in stash_by_tp.items():
                        st.stash[tpid] = parts
        dev = task.selected_device
        for ek, arr in zip(lay.edge_outs, edge_outs):
            if arr is None:
                continue   # a NULL-forwarded flow: successors bind None
            rec.edge_copies[ek] = _edge_copy(arr)
        self.stats["stage_dispatches"] += 1
        self.stats["stage_tasks"] += rec.stage.n_tasks
        if dev is not None:
            dev.stats["tasks"] += rec.stage.n_tasks - 1  # +1 from epilog
        return tuple(tile_outs)

    # ------------------------------------------------------------------ #
    # release: each member's untouched _release_deps over the stash      #
    # ------------------------------------------------------------------ #
    def _release(self, es, rec: _StageRec) -> List[Task]:
        with rec._lock:
            rec.status = _DONE
        # this stage's written tiles are final: prestage the next
        # pending stages' inputs (ISSUE 13 overlap)
        self._prestage_lookahead()
        ready: List[Task] = []
        for inst in rec.stage.members:
            if inst.key not in rec.layout.release_members:
                continue   # every successor is fused into this stage
            tc = self._tc(inst)
            shim = Task(self.tp, tc, inst.locals)
            for i, f in enumerate(tc.ast.flows):
                cp = rec.edge_copies.get((inst.key, f.name))
                if cp is not None:
                    shim.data[i].data_out = cp
            ready.extend(tc._release_deps(
                es, shim, ACTION_RELEASE_ALL) or [])
        rec.edge_copies.clear()
        return ready


def _edge_copy(arr) -> DataCopy:
    """Wrap a stage live-out device array as a deliverable DataCopy
    (the shape _deliver_activation builds for remote arrivals): a
    detached Data whose newest copy holds the (possibly still
    in-flight) device buffer — consumers chain on it like on any
    eager-completed task output."""
    d = Data(nb_elts=int(getattr(arr, "size", 0)))
    cp = DataCopy(d, 0, payload=arr)
    cp.version = 1
    cp.coherency = Coherency.OWNED
    d.attach_copy(cp)
    return cp


def prepared_plan(tp, context) -> StagePlan:
    """The cached, layout-prepared StagePlan of one taskpool under the
    current knobs.  The plan + layouts are a pure function of (spec,
    globals, geometry, distribution, rank) AND the partition knobs —
    max_tasks, wavefront mode, and the exclusion set all join the
    cache key, so a knob change can never hit a stale plan.  Shared by
    ``try_install`` and the chain planner (stagec/chain.declare_chain),
    which therefore always agree on stage identity."""
    from ..devices.batching import cached_stage_callable
    from .plan import _excluded_classes
    # cross-rank SPMD stages (ISSUE 20) need the wave-front partition
    # even without a local chip mesh: every rank must cut the SAME
    # (level, class) waves for the global program to line up
    xrank = bool(params.get("stage_compile_xrank")) \
        and tp.nb_ranks > 1 and bool(params.get("stage_compile_shard"))
    wavefront = xrank or any(
        d.device_type == "tpu" and getattr(d, "mesh", None) is not None
        and len(getattr(d, "chips", ())) > 1 for d in context.devices)
    max_tasks = int(params.get("stage_compile_max_tasks"))

    def build_plan():
        plan = plan_stages(tp, rank=tp.rank, max_tasks=max_tasks,
                           wavefront=wavefront)
        for stage in plan.stages:
            layout = build_layout(tp, plan, stage)
            # the max over the members' TRUE priorities (negative
            # included — a spec that deprioritizes a class must not
            # see its compiled stage boosted to 0)
            prios = [int(m.tc.ast.priority(m.env))
                     for m in stage.members
                     if m.tc.ast.priority is not None]
            plan.prepared.append((stage, layout,
                                  max(prios) if prios else 0))
        # plan-cached startup enumeration (ISSUE 13): goal-0 local
        # residue + the foreign mem-put expectation are pure functions
        # of the plan identity — a stagec _startup skips the whole
        # per-instance iteration-space walk on repeat pools
        for inst in plan.order:
            k = inst.key
            if k in plan.local_keys:
                if k not in plan.member_stage \
                        and inst.tc.goal_of(inst.locals, inst.env) == 0:
                    plan.startup_goal0.append(k)
            else:
                plan.startup_mem_puts += tp._count_mem_puts_to_me(
                    tp.class_by_name(k[0]), inst.env)
        if xrank:
            from .xrank import plan_xwaves
            plan_xwaves(tp, plan, max_tasks)
        return plan

    return cached_stage_callable(
        spec_token(tp),
        ("stageplan", wavefront, xrank, max_tasks,
         _excluded_classes()),
        build_plan)


def try_install(tp, context) -> Optional[StageCompiler]:
    """Build a StageCompiler for ``tp`` when the stage_compile knob is
    on and the pool is eligible; None keeps the interpreted runtime
    bit-for-bit (the knob's off-contract).  The plan + layouts are a
    pure function of (spec, globals, geometry, distribution, rank), so
    they cache under the spec token — a repeat taskpool skips the whole
    enumeration/partition walk, not just the retrace."""
    if not any(d.device_type == "tpu" for d in context.devices):
        return None
    try:
        plan = prepared_plan(tp, context)
    except Exception as exc:  # noqa: BLE001 - unenumerable: interpret
        plog.debug.verbose(
            2, "stagec: %s not plannable (%s: %s); interpreted path",
            tp.name, type(exc).__name__, exc)
        return None
    if not plan.stages:
        return None
    plog.debug.verbose(
        3, "stagec: %s rank %d -> %d stage(s) covering %d/%d local "
        "task(s), %d residue", tp.name, tp.rank, len(plan.stages),
        plan.n_staged, plan.n_local, plan.n_residue)
    return StageCompiler(tp, context, plan)
