"""XLA/TPU device module: asynchronous offload engine over jax.

Reference behavior reproduced (from the CUDA module, SURVEY.md §2.5, §3.4):
- the accelerator chore hands the task to a per-device mini-scheduler and
  returns HOOK_RETURN_ASYNC; the first thread to submit becomes the device
  *manager* (atomic mutex CAS, ref: device_cuda_module.c:2574-2577), others
  just enqueue to ``pending``;
- stage-in reserves device space, pulls the newest copy, and respects the
  coherency protocol (parsec_gpu_data_reserve_device_space / push,
  ref: device_cuda_module.c:864-1040, 2099-2195);
- two LRU lists (clean / dirty-owned) drive eviction with writeback
  (ref: device_gpu.h:128-129);
- per-stream in-flight tracking with events → here jax async dispatch with
  readiness polling (progress_stream, ref: device_cuda_module.c:1961-2012);
- the epilog hands ownership back OWNED→SHARED and bumps versions
  (ref: device_cuda_module.c:2365-2430).

TPU-native re-design: "streams" are jax's async dispatch queues — device_put
and jitted execution return immediately; completion is observed with
``jax.Array.is_ready``-style polling (committed arrays). Kernel bodies are
jax-jit callables (XLA) or Pallas kernels; the runtime caches the jitted
callable per task class. HBM capacity is tracked by payload accounting; an
eviction drops our reference (clean) or writes back to host first (owned).
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import OrderedDict
from operator import itemgetter
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..core.lists import Dequeue
from ..data.data import Coherency, Data, DataCopy, FlowAccess
from ..obs.phases import BRACKETS, _now
from ..runtime.scheduling import hand_over_kept
from ..runtime.taskpool import HookReturn, Task
from ..utils import logging as plog
from ..utils.params import params
from .device import Device

_log = plog.device_stream

#: declared lock discipline, enforced by the concurrency lint
#: (parsec_tpu/analysis/lock_check.py): HBM accounting + both LRU lists
#: belong to the memory lock (any worker stages in / prefetches while
#: the manager evicts); the in-flight/window records (one per device
#: CALL) and the running count of the tasks the window's calls hold
#: belong to the manager lock (one manager at a time — the CAS-owner
#: acquire in ``progress``; helpers on that path carry ``# holds:``
#: annotations), and so does what the manager decides from at a
#: ``wait()``'s exit
_GUARDED_BY = {
    "JaxDevice.mem_used": "_mem_lock",
    "JaxDevice.mem_highwater": "_mem_lock",
    "JaxDevice._lru_clean": "_mem_lock",
    "JaxDevice._lru_owned": "_mem_lock",
    "JaxDevice._window": "_manager_lock",
    "JaxDevice._window_tasks": "_manager_lock",
    "JaxDevice._eager_done": "_manager_lock",
    "JaxDevice._backlog": "_manager_lock",
    "JaxDevice._landing": "_manager_lock",
    "JaxDevice._stage_bound": "_manager_lock",
    "JaxDevice._wait_mark": "_manager_lock",
}

#: the host bytes at which a chunk of a drained ready set closes
#: (``JaxDevice._dispatch_ready``): the manager copies that much in ONE
#: list ``device_put``, dispatches the tasks that waited for it, and
#: only then copies on, so the chip computes under the rest of a wide
#: front's copy instead of after it (the tiles of one list put become
#: ready together, at its end: a front in one put hides nothing).  The
#: v5e's host copies 13.8 GB/s to the chip, so 128 MiB keeps the
#: chip's wait for a front's first kernel under 10 ms: eight NB = 2048
#: tiles (one x8 call), one (32768, 1024) block column (larger than any
#: bound tried: one a chunk), 128 tiles of 1 MB, so a 16-task bucket of
#: small tiles (48 at most) never splits.  Chosen among 32 / 64 / 128
#: MiB (PERF.md section 5).  The probe (``perfbench/checks/
#: stage_front_probe.py``), two puts in flight: the three read the
#: copy's own time within 5% at large tiles (146.8 / 148.7 / 153.2 ms
#: for 120 x 16.8 MB, the copy alone 146.0) and within 1% of each
#: other at 1 MB tiles.  The cells decided between 64 and 128: every
#: chunk is a device call or more (0.28 ms + 74 us a task of the
#: manager), and where the manager is the bound
#: (``dpotrf-mp.n32768-nb2048``) 64 MiB LOST 2.7% to the pass before
#: chunks and 128 gains 4.8%; where the chip is (``dgemm.n24576-
#: nb2048``) 128 gains 12.7% and 64 gained 15.7% (the chip waits 5 ms
#: longer for each k-step's first tiles); the others read the same.
#: Observed in ``src.payload.nbytes``; not a parameter.
#:
#: This is the bound a set must pass to be cut ONLY where the chip has
#: been making its manager wait (``CHIP_WAIT_SHARE``, below): a copy is
#: worth hiding only where the chip's work is what the call waits for.
STAGE_CHUNK_BYTES = 128 << 20

#: what decides between the two bounds (``JaxDevice._note_wait``, once
#: a ``wait()``): over the manager's last finished wait, the wall it
#: was blocked on its chip (bracket ``chip_wait``) against the wall it
#: WORKED (the five other brackets less ``first_call_ns``).  Under a
#: tenth the manager is the bound: every chunk is a device call or
#: more, smaller buckets and one more pass, and the chip catches up
#: behind the manager either way, so a set is cut only over
#: ``STAGE_WHOLE_FACTOR`` times the bound (1 GiB).  Not "never": a
#: put's host staging copy lives until its DMA ends, and two puts in
#: flight of 1 GiB stage 2 GiB at most where one put of a 6.7 GB front
#: (``stencil1d.n40960-nb4096-i100``, whose process holds 34-35 of the
#: machine's 40 GiB) could end the process.  What a manager reads, a
#: call (chip runs, PR 47: least / median / largest over the window's
#: calls, the number of readings in brackets; four managers in the
#: four-chip cell), cutting at 128 MiB | at 1 GiB, and what PR 44's
#: chunks had done to ``factor_s`` (ledger, PR 44):
#:
#:   dgetrf.n32768-nb1024  2.16 / 2.36 / 2.88 (28)     |        -17.4%
#:   dpotrf.n32768-nb2048  0.43 / 0.55 / 0.72 (28)     |        -15.5%
#:   dgetrf.n16384-nb512   0.34 / 0.41 / 0.49 (28)     |         -6.8%
#:   dgemm.n24576-nb2048   0.22 / 0.29 / 0.46 (27; one 0.065) | -12.0%
#:   dpotrf-mp.n32768      0.097 / 0.165 / 0.218 (82)  | 0.42, 0.51 (2)  -6.7%
#:   ---- a tenth ----
#:   dgemm-dtd-4chip .013 / .039 / .064 (56) | .047 / .075 / .127 (64) +18.2%
#:   stencil1d.n40960 .052 / .066 / .070 (8) | .076 / .087 / .092 (8) PR 46's
#:   dpotrf-dtd.n20480 .041 / .048 / .082 (9) | .039 / .043 / .048 (9) +1.8%
#:   dpoinv.n16384 .030 / .036 / .038 (6) | .034 / .037 / .039 (6) +2.0%
#:   dpotrf.n16384 .033 / .038 / .127 (28) | .010 / .037 / .079 (28) +4.9%
#:
#: The medians stand far apart whichever way the manager last decided
#: (0.165 and more against 0.09 and less), so the answer does not
#: flip-flop; single calls stray by a third of their reading and more:
#: of ``dpotrf-mp``'s 82 chunked calls ten read under an eighth (the
#: ledger's means had suggested an eighth) and two under a tenth; of
#: the 64 readings of ``dgemm-dtd-4chip``'s managers one read over
#: either (0.127).  Hence a tenth; a call that strays costs the next
#: call of its device 3 to 8%, which then reads back on its own side
#: (``dpotrf-mp``: one call in thirty takes one set whole).  Observed
#: in the device's own ``stats``; not parameters.
CHIP_WAIT_SHARE = 1 / 10
STAGE_WHOLE_FACTOR = 8

#: tasks the window's calls may hold after dispatch before the manager
#: blocks on the oldest call: bounds how far the chip's queue runs ahead
#: (the reference bounds in-flight work per stream)
EAGER_WINDOW = 32


def _arr_device(arr: Any):
    """The single device committing ``arr``, or None (host / sharded)."""
    try:
        devs = arr.devices()
        if len(devs) == 1:
            return next(iter(devs))
    except (AttributeError, TypeError):
        pass
    return None


class _Xfer:
    """One host<->device transfer, timed for whoever listens: the
    telemetry sink (``DeviceObs.xfer``: histogram, overlap gauge, trace
    span) and, for a stage-in, the open root span's phase clock."""

    __slots__ = ("obs", "clock", "direction", "nbytes", "args", "t0")

    def __init__(self, obs, clock, direction: str, nbytes: int,
                 args: Dict[str, Any]) -> None:
        self.obs, self.clock = obs, clock
        self.direction, self.nbytes, self.args = direction, nbytes, args
        self.t0 = 0

    def __enter__(self) -> None:
        if self.clock is not None:
            self.clock.push("stage_in", bytes=self.nbytes, **self.args)
        self.t0 = time.monotonic_ns()

    def __exit__(self, *exc) -> bool:
        if self.obs is not None:
            self.obs.xfer(self.direction, self.nbytes, self.t0)
        if self.clock is not None:
            self.clock.pop("stage_in")
        return False


_NO_XFER = contextlib.nullcontext()


class _InFlight:
    """The record of ONE device call of n >= 1 tasks: a stacked or
    mesh-sharded call files its whole chunk, a task dispatched alone a
    record of one.  Everything after the dispatch (the window, the
    wait, the retire, the epilog, the hand-over of what became ready)
    happens once per record.

    ``outs`` is the call's flat result, grouped by output slot as the
    stacked programs return it: ``outs[k * n + i]`` is output ``k`` of
    ``tasks[i]``, written to flow ``out_flows[i][k]`` (a DTD task
    carries its own access modes, so the written flows are each
    task's).  ``waits`` groups the outputs by the executable that makes
    them: the members of a group become ready together, so one live
    member answers for the group (a stacked call is one group; a lone
    task's eager kernels are a group each; a sharded call has one per
    chip)."""

    __slots__ = ("tasks", "outs", "out_flows", "waits", "est", "t0",
                 "last_poll", "done_est")

    def __init__(self, tasks: List[Task], outs: Sequence[Any],
                 out_flows: List[List[int]], est: float,
                 waits: Optional[List[Sequence[Any]]] = None) -> None:
        self.tasks = tasks
        self.outs = outs
        self.out_flows = out_flows
        self.waits = [outs] if waits is None else waits
        self.est = est      # summed over the call's tasks
        # submission timestamp: with telemetry on, [t0, completion
        # estimate] feeds the live overlap gauge's COMPUTE channel as
        # the device-busy interval (obs/spans.OverlapTracker; exec PINS
        # spans only see the async hook, not the kernel).  The kernel's
        # true finish lies between the last poll that saw it NOT ready
        # (last_poll) and the poll that saw it ready — the poll loops
        # stamp the midpoint into done_est so a slow poll cadence (a
        # progress thread sleeping in a throttled send) cannot inflate
        # the busy window by a whole poll gap and silently "hide" its
        # own comm time under it.
        self.t0 = time.monotonic_ns()
        self.last_poll = self.t0
        self.done_est = 0

    def live(self):
        """One output per wait group that still has a buffer to ask.  A
        DONATED buffer (device_donate: a successor's call consumed it)
        is that successor's record's to wait for — donation happens at
        the consumer's dispatch, which XLA orders after this producer —
        and a host array is always ready."""
        for group in self.waits:
            for a in group:
                if hasattr(a, "block_until_ready") and not a.is_deleted():
                    yield a
                    break

    def ready(self) -> bool:
        """Is the call's work materialized (the event-query analog)?
        Asked once per wait group."""
        return all(a.is_ready() for a in self.live())


class JaxDevice(Device):
    """One jax.Device managed as a PaRSEC accelerator device."""

    def __init__(self, device_index: int, jax_device: Any) -> None:
        plat = getattr(jax_device, "platform", "tpu")
        super().__init__("tpu", device_index, name=f"{plat}:{jax_device.id}")
        self.jax_device = jax_device
        self.time_estimate_default = 1.0
        # device manager state (ref: gpu_device->mutex + pending)
        self.pending = Dequeue()
        self._manager_lock = threading.Lock()
        # memory accounting + LRU (ref: zone_malloc + gpu_mem_lru/_owned_lru)
        self.mem_budget = self._probe_budget()
        self.mem_used = 0
        self.mem_highwater = 0  # HBM accounting high-water mark (gauge)
        self._lru_clean: "OrderedDict[int, DataCopy]" = OrderedDict()
        self._lru_owned: "OrderedDict[int, DataCopy]" = OrderedDict()
        self._mem_lock = threading.Lock()
        self.stats = {"stage_in_bytes": 0, "stage_out_bytes": 0,
                      "evictions": 0, "tasks": 0,
                      # batched-dispatch pipeline telemetry (guide §9.1)
                      "batches": 0, "batched_tasks": 0,
                      "dispatch_tasks": 0,
                      # the part of dispatch_ns spent in each program's
                      # first call on this device (trace + lower + load)
                      "first_call_ns": 0, "first_calls": 0,
                      # stacked or sharded dispatches whose program an
                      # earlier taskpool built (cached by token)
                      "program_reuse": 0,
                      # the part of stage_in_bytes pulled from a copy
                      # on another chip
                      "stage_in_peer_bytes": 0,
                      # chip-to-chip ``device_put`` calls issued for a
                      # stage-in, a tile each (a Data's tile whose
                      # newest copy another chip holds; a runtime-made
                      # buffer another chip's task wrote), and the wall
                      # ns of those calls on the manager's thread:
                      # inside ``group`` (the per-task stage-in is), not
                      # a bracket of their own
                      "peer_pulls": 0, "peer_pull_ns": 0,
                      # ``device_put`` calls that moved tiles here for
                      # a stage-in, from the host or another chip (the
                      # one call of a set's list is one), and the tiles
                      # they carried
                      "stage_in_transfers": 0, "stage_in_tiles": 0,
                      # list puts the set pass issued, one a chunk of a
                      # drained ready set; and tasks dispatched while
                      # host tiles of their own set were still to be
                      # copied (resident tasks sent ahead of a set over
                      # the bound, every chunk but its last); and sets
                      # over ``STAGE_CHUNK_BYTES`` that went whole all
                      # the same, because the chip had not been making
                      # this manager wait (``_note_wait``)
                      "stage_chunks": 0, "tasks_ahead_of_copy": 0,
                      "sets_whole_by_wait": 0,
                      # tiles ``prestage_many`` staged ahead of the
                      # per-task stage-in / found there by it
                      "prefetch_issued": 0, "prefetch_hits": 0,
                      "donated": 0,
                      # every rung a dispatch gave up (a run that must
                      # prove it stayed on the fast path asserts zero):
                      # stacked -> per-task, donated -> undonated retry
                      "batch_downgrades": 0, "donate_retries": 0,
                      # call records retired: tasks / retired_calls is
                      # the tasks a record held
                      "retired_calls": 0,
                      # which rule of get_best_device sent a task here:
                      # the device owned a tile the task writes, or
                      # first touch (where advised, else least load)
                      "placed_by_owner": 0, "placed_by_advice": 0,
                      "placed_by_load": 0,
                      # parts of compound taskpools (runtime/compound.py)
                      # whose first device call left from here
                      "compound_parts": 0,
                      # the reshape engine (data/reshape.py) on tiles
                      # that live here: conversions made on this chip
                      # and the bytes of the copies they made, lookups
                      # an earlier conversion answered, and the wall ns
                      # and count of the passes through the engine
                      "conversions": 0, "conversion_bytes": 0,
                      "reshape_hits": 0, "reshape_ns": 0, "reshape_n": 0,
                      # runtime-made buffers that are nobody's Data (a
                      # WRITE-only flow's, handed from the task that
                      # writes it to its readers): bytes copied here
                      # from host memory for a stage-in (0 unless a
                      # host body made the buffer), and bytes tasks
                      # here wrote into such buffers
                      "scratch_stage_in_bytes": 0, "scratch_out_bytes": 0}
        # the manager's always-on brackets (obs.phases.BRACKETS), one
        # per place it works under ``_manager_lock`` and none per task:
        # wall ns (``time.monotonic_ns``, a vDSO read) and how many.
        # Disjoint: a bracket inside another is taken out of it.
        # ``set_stage``: the set pass, once a chunk of a drained set;
        # ``group``: the rest of that pass of ``_dispatch_ready`` less
        # the two inside it; ``dispatch``:
        # the device call (``first_call_ns`` is its part);
        # ``chip_wait``: ``_retire``'s wait for the call's outputs;
        # ``epilog``: ``_epilog`` up to ``complete_executions``;
        # ``complete``: that call.  A root span's record holds what
        # they moved by (obs/phases.py: ``manager``).  No bracket reads
        # the thread's CPU clock: a system call, and on the v5e's host a
        # fresh value costs 0.6 ms (PERF.md section 6, PR 35).
        for b in BRACKETS:
            self.stats.update({b + "_ns": 0, b + "_n": 0})
        # completion: dependencies release at dispatch (XLA orders the
        # dataflow); the calls dispatched and not yet waited for are the
        # window, bounded in tasks (``EAGER_WINDOW``)
        self._window: List[_InFlight] = []
        self._window_tasks = 0      # tasks the window's calls hold
        self._eager_done: List[_InFlight] = []
        # drained tasks that wait for host tiles a later chunk of their
        # set brings, with the bytes each waited for when it arrived,
        # least first (``_dispatch_ready``)
        self._backlog: List[Tuple[Task, float, int]] = []
        # the last tile of each of the set pass's last two puts: a put
        # waits until the one before last has landed
        # (``_stage_in_set``)
        self._landing: List[Any] = []
        # what a drained set's new host bytes must pass before it is
        # cut into chunks, decided once a ``wait()`` (``_note_wait``)
        # from ``wait_reading``: the (``chip_wait``, work) ns of this
        # manager's last finished wait, None until one has retired a
        # call; ``_wait_mark``: the counters as that wait left them
        self._stage_bound = STAGE_CHUNK_BYTES
        self.wait_reading: Optional[Tuple[int, int]] = None
        self._wait_mark = (0, 0, 0)
        # batched dispatch (the task-stream pipeline; ISSUE 5):
        # same-class ready tasks accumulate in ``pending`` and are
        # stacked into one jitted call per (class, shapes, dtypes,
        # bucket) at the next manager flush
        self.batch_max = int(params.get("device_batch_max"))
        #: signatures whose programs ``_build_ahead`` built here
        self._built_ahead: set = set()
        # read by the stage compiler's prestager and the tuner only:
        # the manager stages a drained set by chunks of bytes
        # (``_dispatch_ready``)
        self.prefetch_depth = int(params.get("device_prefetch_depth"))
        self.donate = bool(params.get("device_donate"))
        # copies ``prestage_many`` staged: id(copy) -> version; a
        # per-task stage-in that finds its copy here still valid is a
        # HIT
        self._prefetched: Dict[int, int] = {}

    def _probe_budget(self) -> int:
        stats = self.jax_device.memory_stats() or {}
        limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
        if limit:
            return int(limit * params.get("tpu_memory_fraction_pct") / 100)
        if self.jax_device.platform == "tpu":
            # the LRU's budget IS the chip's HBM: guessing one would
            # either evict what fits or overcommit what does not
            raise RuntimeError(
                f"{self.jax_device}: memory_stats() reports no "
                f"bytes_limit ({sorted(stats)}); cannot size the HBM "
                f"budget of a TPU device")
        # XLA's host (CPU) backend reports no limit: 8 GiB of
        # accounting space for the virtual-device test substrate
        return 8 << 30

    # ------------------------------------------------------------------ #
    # submission: the accelerator chore calls this and returns ASYNC     #
    # ------------------------------------------------------------------ #
    def kernel_scheduler(self, es, task: Task) -> HookReturn:
        """ref: parsec_cuda_kernel_scheduler (device_cuda_module.c:2537)."""
        task.selected_device = self
        est = (task.task_class.time_estimate(task, self)
               if task.task_class.time_estimate else self.time_estimate_default)
        self.load_add(est)
        task.es_hint = es.th_id
        self.pending.push_back((task, est))
        chore = task.task_class.incarnations[task.selected_chore]
        spec = getattr(chore, "batch_spec", None)
        if spec is not None and spec.batchable and self.batch_max > 1 \
                and len(self.pending) < self.batch_max:
            # accumulate: a same-class burst becomes ONE stacked
            # dispatch at the next manager flush (idle workers call
            # progress() every cycle, so the deferral is bounded by the
            # releasing worker's remaining ready tasks)
            return HookReturn.ASYNC
        # queue full (or batching off): become the manager right away
        # (first thread wins)
        self.progress(es)
        return HookReturn.ASYNC

    # ------------------------------------------------------------------ #
    # the manager loop, run opportunistically from idle workers          #
    # ------------------------------------------------------------------ #
    def progress(self, es) -> int:
        if not self._manager_lock.acquire(blocking=False):
            return 0  # someone else is the manager (CAS-owner pattern)
        clock = self._phases
        if clock is not None:
            clock.push("manager")
        try:
            n = 0
            while True:
                # push phase: drain everything pending and dispatch it —
                # same-class/same-shape tasks as stacked batches, the
                # rest per task.  Submissions count as progress (they
                # advance the pipeline even when no completion is ready
                # yet).
                drained: List[Tuple[Task, float]] = []
                while True:
                    item = self.pending.pop_front()
                    if item is None:
                        break
                    drained.append(item)
                if drained or self._backlog:
                    n += self._dispatch_ready(es, drained)
                n += self._poll(es)
                if not self._backlog:
                    return n
                # a set over the bound left tasks waiting for their
                # chunk: what the poll released and what arrived under
                # the chunk's copy is drained first.  The best of what
                # this thread released was kept for it to run next, and
                # it is not going back to its loop: the other workers
                # get it
                hand_over_kept(es)
        finally:
            if clock is not None:
                clock.pop("manager")
            self._manager_lock.release()

    def _poll(self, es) -> int:  # holds: self._manager_lock
        """The poll phase: the epilogs of the calls dispatched since the
        last one (they release their successors), and the retirement of
        the window's calls that have become ready.  Returns the tasks
        completed."""
        n = 0
        if self._eager_done:
            done, self._eager_done = self._eager_done, []
            for rec in done:
                self._epilog(es, rec)
                n += len(rec.tasks)
        if self._window:
            now = time.monotonic_ns()
            # retire finished window entries so device_load drains on
            # idle devices and async errors surface during the run
            still_w = []
            for rec in self._window:
                if rec.ready():
                    rec.done_est = (rec.last_poll + now) // 2
                    self._window_tasks -= len(rec.tasks)
                    self._retire(rec, es)
                else:
                    rec.last_poll = now
                    still_w.append(rec)
            self._window = still_w
        return n

    def _book(self, bracket: str, wall_ns: int) -> None:
        """Close one always-on bracket of the manager."""
        st = self.stats
        st[bracket + "_ns"] += wall_ns
        st[bracket + "_n"] += 1

    # ------------------------------------------------------------------ #
    # stage-in / execute                                                 #
    # ------------------------------------------------------------------ #
    def _xfer(self, direction: str, nbytes: int, **args: Any):
        """``with self._xfer("in", nbytes): <the transfer>`` — the one
        timing site of every stage-in / stage-out; a shared no-op
        unless telemetry or a root span listens.  ``args`` go with a
        stage-in's span (``cls="peer"``: pulled from another chip)."""
        obs = self._obs
        clock = self._phases if direction == "in" else None
        if obs is None and clock is None:
            return _NO_XFER
        return _Xfer(obs, clock, direction, nbytes, args)

    def _stage_in(self, task: Task,
                  donate_ok: Optional[Dict[int, bool]] = None) -> List[Any]:
        """Resolve every input flow to an array on this device
        (ref: parsec_cuda_kernel_push, device_cuda_module.c:2099-2195).

        ``donate_ok`` (flow_index -> bool), when given, marks WRITE
        flows whose device buffer is exclusively ours — either freshly
        device_put here or device-resident with no readers — and hence
        safe to donate to a batched call."""
        clock = self._phases
        if clock is None:
            return self._stage_in_flows(task, donate_ok)
        clock.push("stage_in", cls=task.task_class.name)
        try:
            return self._stage_in_flows(task, donate_ok)
        finally:
            clock.pop("stage_in")

    def _stage_in_flows(self, task: Task,
                        donate_ok: Optional[Dict[int, bool]]) -> List[Any]:
        import jax
        from ..data.data import is_device_array
        target = self._stage_target(task)
        arrays: List[Any] = []
        for flow in task.task_class.flows:
            access = task.access_of(flow)
            ref = task.data[flow.flow_index]
            if flow.ctl or ref.data_in is None:
                arrays.append(None)
                continue
            data = ref.data_in.data
            if data is None:
                # nobody's Data: a runtime-made buffer handed from task
                # to task (a WRITE-only flow's, a converted tile)
                payload = ref.data_in.payload
                if payload is None:
                    # not written yet: this task's output is its first
                    # value, the body gets no argument for it
                    arrays.append(None)
                    continue
                if donate_ok is not None and access & FlowAccess.WRITE:
                    donate_ok[flow.flow_index] = True
                from_host = not is_device_array(payload)
                if from_host:   # a host body made it
                    self.stats["stage_in_transfers"] += 1
                    self.stats["stage_in_tiles"] += 1
                    self.stats["scratch_stage_in_bytes"] += getattr(
                        payload, "nbytes", 0)
                if from_host:
                    payload = jax.device_put(payload, target)
                elif _arr_device(payload) is not target:
                    payload = self._peer_pull(payload, target)
                arrays.append(payload)
                continue
            copy = data.get_copy(self.device_index)
            if copy is None:
                copy = DataCopy(data, self.device_index, payload=None,
                                dtt=ref.data_in.dtt)
                data.attach_copy(copy)
            src = data.start_transfer_ownership(self.device_index, access)
            if src is not None:
                nbytes = getattr(src.payload, "nbytes", 0)
                # credit the stale payload being replaced before reserving
                self._account(-getattr(copy.payload, "nbytes", 0))
                self._reserve(nbytes)
                if is_device_array(src.payload):
                    copy.payload = self._peer_pull(
                        src.payload, self._placement(data, target))
                    self.stats["stage_in_peer_bytes"] += nbytes
                else:
                    with self._xfer("in", nbytes):
                        copy.payload = jax.device_put(
                            src.payload, self._placement(data, target))
                self.stats["stage_in_bytes"] += nbytes
                self.stats["stage_in_transfers"] += 1
                self.stats["stage_in_tiles"] += 1
                self._prefetched.pop(id(copy), None)  # staged-over: stale
            elif self._prefetched.pop(id(copy), None) is not None:
                # the set pass staged this tile with its drained set
                # and the version held
                self.stats["prefetch_hits"] += 1
            data.complete_transfer_ownership(self.device_index, access)
            self._lru_touch(copy, owned=bool(access & FlowAccess.WRITE))
            if donate_ok is not None and access & FlowAccess.WRITE \
                    and copy.readers == 0:
                donate_ok[flow.flow_index] = True
            arrays.append(self._localize(copy.payload, target))
        return arrays

    def _peer_pull(self, payload: Any, placement: Any) -> Any:
        """ONE chip-to-chip ``device_put`` of a tile another chip holds,
        for a stage-in: counted (``peer_pulls``), its wall on this
        manager's thread kept (``peer_pull_ns``), and with a phase
        clock a ``stage_in`` span of class ``peer``."""
        import jax
        t0 = _now()
        with self._xfer("in", getattr(payload, "nbytes", 0), cls="peer"):
            pulled = jax.device_put(payload, placement)
        st = self.stats
        st["peer_pulls"] += 1
        st["peer_pull_ns"] += _now() - t0
        return pulled

    # mesh seam (JaxMeshDevice overrides; the single-chip base is the
    # identity so the pre-mesh behavior is byte-for-byte unchanged)
    def _stage_target(self, task: Task) -> Any:
        """The chip a task's inputs are colocated on for dispatch."""
        return self.jax_device

    def _placement(self, data: Data, target: Any) -> Any:
        """The chip a tile's resident device copy lives on."""
        return target

    def _localize(self, payload: Any, target: Any) -> Any:
        """Make a staged payload usable on ``target`` (transient
        chip-to-chip hop on a mesh; identity on a single chip)."""
        return payload

    def _note_profile(self, es, cls_name: str, us_per_task: float,
                      n: int) -> None:
        """Feed the context's online class profile (critical-path-driven
        scheduler priorities, ISSUE 7) with this class's measured
        dispatch cost — one dict lookup + None check when profiling is
        off."""
        ctx = getattr(es, "context", None) if es is not None else None
        prof = getattr(ctx, "class_profile", None)
        if prof is not None:
            prof.note(cls_name, us_per_task, n)

    def _out_flows(self, task: Task) -> List[int]:
        return [f.flow_index for f in task.task_class.flows
                if (task.access_of(f) & FlowAccess.WRITE) and not f.ctl
                and task.data[f.flow_index].data_in is not None]

    def _submit(self, es, task: Task, est: float) -> None:
        self._submit_prepared(es, task, est, self._stage_in(task))

    def _submit_prepared(self, es, task: Task, est: float,
                         inputs: List[Any]) -> None:
        """Per-task dispatch of an already-staged task (the classic
        path; also the transparent fallback for singleton or
        shape-divergent batches — semantics unchanged)."""
        tc = task.task_class
        chore = tc.incarnations[task.selected_chore]
        fn = chore.dyld_fn
        assert fn is not None, f"tpu chore of {tc.name} has no executable"
        # fn is the DSL's wrapper: (task, per-flow device arrays) -> outputs
        clock = self._phases
        t0 = _now()
        if clock is not None:
            clock.push("dispatch", t0, cls=tc.name, n=1)
        try:
            outputs = fn(task, inputs)
        finally:
            t1 = _now()
            if clock is not None:
                clock.pop("dispatch", tasks=1, at_ns=t1)
        dt = t1 - t0
        self._book("dispatch", dt)
        self.stats["dispatch_tasks"] += 1
        self._note_profile(es, tc.name, dt / 1e3, 1)
        if outputs is None:
            outputs = ()
        elif not isinstance(outputs, (tuple, list)):
            outputs = (outputs,)
        out_flows = self._out_flows(task)
        assert len(outputs) == len(out_flows), (
            f"{tc.name} tpu body returned {len(outputs)} arrays for "
            f"{len(out_flows)} written flows")
        # a record of one; the body's eager kernels are an executable
        # each, so each output is waited for
        self._finish_submit(es, _InFlight(
            [task], outputs, [out_flows], est, [(a,) for a in outputs]))

    def _finish_submit(self, es, rec: _InFlight) -> None:  # holds: self._manager_lock
        """File the record of one dispatched call, whatever it holds:
        once per call, never per task."""
        n = len(rec.tasks)
        self.stats["tasks"] += n
        part = rec.tasks[0].taskpool._part
        if part is not None and not part["first_call_ns"]:
            # the first device call of a part of a compound taskpool
            part["first_call_ns"] = time.monotonic_ns()
            self.stats["compound_parts"] += 1
        # TPU-native completion model: jax dispatch is async and XLA's
        # execution queue already orders consumers after producers, so
        # dependency release need not wait for the kernel — successors
        # chain their jit calls on the in-flight arrays. Host-side
        # reads still block on conversion (device->host sync point).
        # A bounded window keeps the queue from running unboundedly
        # ahead (ref: the CUDA module bounds in-flight per stream):
        # it holds calls and bounds the TASKS they hold.
        self._window.append(rec)
        self._window_tasks += n
        while self._window_tasks > EAGER_WINDOW and len(self._window) > 1:
            # backpressure: block on the oldest call (never on the
            # one just filed: a call larger than the window waits
            # for nothing but its predecessors)
            old = self._window.pop(0)
            self._window_tasks -= len(old.tasks)
            self._retire(old, es)
        self._eager_done.append(rec)

    # ------------------------------------------------------------------ #
    # batched dispatch: stack same-class ready tasks into ONE jitted     #
    # call (devices/batching.py; ISSUE 5 tentpole)                       #
    # ------------------------------------------------------------------ #
    def _dispatch_ready(self, es, items: List[Tuple[Task, float]]) -> int:  # holds: self._manager_lock
        """Dispatch a drained ready set, a CHUNK of its host bytes at a
        time, and return the number of tasks submitted; what it leaves
        in ``_backlog`` the caller's loop hands back after its poll
        phase (``progress``).

        The bound is ``_stage_bound``: ``STAGE_CHUNK_BYTES`` where this
        manager's last finished wait says its chip made it wait (and
        before any has finished), ``STAGE_WHOLE_FACTOR`` times that
        where it says the manager is the bound (``_note_wait``: chunks
        hide a copy under the chip's work, and cost calls and passes
        where nothing waits for the chip).  Counter
        ``sets_whole_by_wait``: sets over the first that went whole
        under the second.

        A set whose host tiles stay under the bound (and
        no backlog before it) is one chunk: ONE list ``device_put``
        (``_stage_in_set``), then ``_dispatch_groups`` over the whole
        set: group by (class, static context, shapes, dtypes, donate
        mask), stack each group into power-of-two buckets, fall back
        per-task for singletons / shape-divergent / unbatchable tasks.

        A set over it: the tasks that wait for no host tile are
        dispatched first (they cost no bytes and never queue behind a
        copy); the others join the backlog, which is kept by the host
        bytes a task waited for when it arrived, least first (arrival
        order, the scheduler's, among equals: a front of equal tiles
        keeps it).  Then the head of the backlog
        up to the bound, closed at a task boundary, is looked at again
        (a tile an earlier chunk brought is not brought again), its
        tiles go in ONE put and its tasks are dispatched right behind
        it: the chip starts on this chunk while the next pass copies
        the next.  Same tasks, same kernels, same per-tile order;
        ``unroll`` stacking is bit-exact however tasks are grouped.

        Two always-on brackets, each booked once a pass: ``set_stage``
        (the looks and the put) and ``group`` (the rest, less the
        ``dispatch`` and ``chip_wait`` brackets closed inside it)."""
        st = self.stats
        backlog = self._backlog
        bound = self._stage_bound
        wall = [0, 0]   # ns of the set pass, ns of the grouping
        n = 0
        try:
            with self._set_pass(wall, len(items)):
                need: Dict[int, Tuple] = {}
                looks = [self._host_need(task, need) for task, _e in items]
                new_bytes = sum(new for _w, new in looks)
                whole = not backlog and new_bytes < bound
                if whole and new_bytes >= STAGE_CHUNK_BYTES:
                    st["sets_whole_by_wait"] += 1
                first = [] if whole else [
                    it for it, (w, _new) in zip(items, looks) if not w]
                backlog.extend((task, est, w) for (task, est), (w, _new)
                               in zip(items, looks) if whole or w)
                if not whole:
                    # least bytes first (stable: arrival order, the
                    # scheduler's, among equals): a later arrival that
                    # waits for little (LU's PANEL(k + 1): its 0.5 MB
                    # pivot tile) does not queue behind the rest of a
                    # front of 134 MB columns
                    backlog.sort(key=itemgetter(2))
            if first:
                n = self._grouped(es, first, wall)
                st["tasks_ahead_of_copy"] += n
            with self._set_pass(wall, len(backlog)):
                k = len(backlog)
                if not whole:
                    need, held, k = {}, 0, 0
                    for task, _e, _w in backlog:
                        k += 1
                        held += self._host_need(task, need)[1]
                        if held >= bound:
                            break
                chunk = backlog[:k]
                del backlog[:k]
                try:
                    self._stage_in_set(need)
                except Exception as exc:
                    plog.warning("tpu set stage-in failed: %s", exc)
                    # nothing of the chunk was dispatched: it goes BACK
                    # to the head of the backlog, for the next pass or
                    # for the abort path's drain() to credit its load
                    backlog[:0] = chunk
                    raise
            k = self._grouped(es, [it[:2] for it in chunk], wall)
            if backlog:
                st["tasks_ahead_of_copy"] += k
            return n + k
        finally:
            self._book("set_stage", wall[0])
            self._book("group", wall[1])

    @contextlib.contextmanager
    def _set_pass(self, wall: List[int], n: int):
        """A stretch of the set pass: its wall ns join ``wall[0]``;
        with a phase clock, a ``stage_in`` span of class ``set``."""
        clock = self._phases
        t0 = _now()
        if clock is not None:
            clock.push("stage_in", t0, cls="set", n=n)
        try:
            yield
        finally:
            t1 = _now()
            if clock is not None:
                clock.pop("stage_in", at_ns=t1)
            wall[0] += t1 - t0

    def _grouped(self, es, items: List[Tuple[Task, float]],
                 wall: List[int]) -> int:
        """``_dispatch_groups`` with its wall ns, less the ``dispatch``
        and ``chip_wait`` brackets closed inside, joining ``wall[1]``."""
        st = self.stats
        t0 = _now()
        inside = st["dispatch_ns"] + st["chip_wait_ns"]
        try:
            return self._dispatch_groups(es, items)
        finally:
            wall[1] += _now() - t0 - (
                st["dispatch_ns"] + st["chip_wait_ns"] - inside)

    def _dispatch_groups(self, es, items: List[Tuple[Task, float]]) -> int:
        """``_dispatch_ready`` behind a chunk's put: the per-task
        stage-in, the grouping and every dispatch of ``items``."""
        from .batching import bucket_size, settle
        groups: Dict[Any, List[Tuple]] = {}
        order: List[Any] = []   # dispatch groups in arrival order
        n = 0
        for idx, (task, est) in enumerate(items):
            try:
                chore = task.task_class.incarnations[task.selected_chore]
                spec = getattr(chore, "batch_spec", None)
                if spec is None or not spec.batchable or self.batch_max <= 1:
                    self._submit(es, task, est)
                    n += 1
                    continue
                donate_ok: Dict[int, bool] = {}
                inputs = self._stage_in(
                    task, donate_ok if self.donate else None)
                ext = spec.extract(task, inputs)
                if ext is None:
                    self._submit_prepared(es, task, est, inputs)
                    n += 1
                    continue
                bargs, flow_idx, static = ext
                donate = tuple(bool(donate_ok.get(fi)) for fi in flow_idx)
                # what is true of the call is computed once for the
                # call: the key carries the shapes as they are (both
                # members hash), down to the program cache
                shapes = tuple((a.shape, a.dtype) for a in bargs)
                key = (spec, static, shapes, donate)
                if key not in groups:
                    groups[key] = []
                    order.append(key)
                groups[key].append((task, est, inputs, bargs))
            except Exception as exc:  # surfacing beats hanging the DAG
                plog.warning("tpu submit failed for %s: %s",
                             task.snprintf(), exc)
                # the failing task is lost (its load is credited here);
                # drained-but-untouched siblings and grouped entries go
                # BACK to pending so a later progress dispatches them —
                # or the abort path's drain() credits their load
                self.load_sub(est)
                for g in groups.values():
                    for t2, e2, _inp, _ba in g:
                        self.pending.push_back((t2, e2))
                for t2, e2 in items[idx + 1:]:
                    self.pending.push_back((t2, e2))
                raise
        for gidx, key in enumerate(order):
            spec, static, shapes, donate = key
            g = groups[key]
            try:
                if (len(g) >= 2 or spec.ahead > 1) \
                        and spec.late_token is not None \
                        and not settle(spec):
                    # the spec's first stacked dispatch named its
                    # programs, and an earlier taskpool's trace of them
                    # failed: given up again, without tracing
                    self.stats["batch_downgrades"] += 1
                if spec.ahead > 1 and spec.batchable and not any(donate):
                    self._build_ahead(spec, static, shapes, g[0])
                # re-check batchable each bucket: a trace failure in the
                # first chunk must not re-trace/re-fail the rest
                while len(g) >= 2 and spec.batchable:
                    b = bucket_size(len(g), self.batch_max)
                    chunk, g = g[:b], g[b:]
                    self._dispatch_stacked(es, spec, static, shapes,
                                           donate, chunk)
                    n += b
                while g:   # singleton / post-downgrade remainder
                    task, est, inputs, _ = g.pop(0)
                    self._submit_prepared(es, task, est, inputs)
                    n += 1
            except Exception as exc:
                plog.warning("tpu batch dispatch failed for %s: %s",
                             spec.name, exc)
                for t2, e2, _inp, _ba in g:   # undispatched of this group
                    self.pending.push_back((t2, e2))
                for k2 in order[gidx + 1:]:   # untouched later groups
                    for t2, e2, _inp, _ba in groups[k2]:
                        self.pending.push_back((t2, e2))
                raise
        return n

    def _build_ahead(self, spec, static, shapes, entry: Tuple) -> None:
        """Build on this device every program of a class whose operation
        said how many of its tasks a device can hold at once
        (``spec.ahead``): each stacked bucket up to that many, then the
        lone task's, called on the arrays of ``entry`` (the first task
        of the device's first group of this signature; a bucket of
        ``b`` gets them ``b`` times) with the results dropped.  Once a
        signature a device; a program the device has called is not
        called again.  The wall is ``first_call_ns`` inside the
        ``dispatch`` bracket and no call is counted: nothing was
        dispatched.  A failure is the real dispatch's to meet."""
        from .batching import bucket_size, cached_stacked_callable
        key = (spec.cache_token, static, shapes)
        if spec.cache_token is None or key in self._built_ahead:
            return
        self._built_ahead.add(key)
        task, _est, inputs, bargs = entry
        nargs = len(shapes)
        clock = self._phases
        t0 = _now()
        if clock is not None:
            clock.push("first_call", t0, cls=task.task_class.name, n=0)
        built = 0
        try:
            b, top = 2, bucket_size(spec.ahead, self.batch_max)
            while b <= top:
                fn = cached_stacked_callable(spec, b, nargs, static,
                                             shapes, (False,) * nargs)
                if fn.first_call_on(self.name):
                    fn(*[a for a in bargs for _ in range(b)])
                    built += 1
                b *= 2
            task.task_class.incarnations[task.selected_chore].dyld_fn(
                task, inputs)
        except Exception as exc:
            plog.warning("building %s ahead failed (%s: %s)", spec.name,
                         type(exc).__name__, exc)
        t1 = _now()
        if clock is not None:
            clock.pop("first_call", tasks=0, at_ns=t1)
        self.stats["dispatch_ns"] += t1 - t0
        self.stats["first_call_ns"] += t1 - t0
        self.stats["first_calls"] += built

    def _dispatch_stacked(self, es, spec, static, shapes, donate,
                          chunk: List[Tuple]) -> None:
        """ONE stacked jitted call for ``chunk``, filed as ONE record;
        the lowered callable is AOT-cached on the spec per (bucket,
        static, shapes, donate) so steady-state submission is a cache
        hit (``shapes`` is the group key's, not derived again).  Any
        trace/dispatch failure (untraceable body, backend quirk)
        permanently downgrades the spec to per-task dispatch —
        semantics are never at risk."""
        from .batching import cached_stacked_callable, downgrade
        n = len(chunk)
        nargs = len(shapes)
        flat = [entry[3][j] for j in range(nargs) for entry in chunk]
        if any(donate) and len({id(x) for x in flat}) != len(flat):
            # the same buffer appears at two argument slots (a task
            # whose flows alias one tile, e.g. f(x, x)): donating it
            # while another slot still reads it is XLA's canonical
            # `f(donate(a), a)` error — keep the batch, drop donation
            donate = tuple(False for _ in donate)
        fn = cached_stacked_callable(spec, n, nargs, static, shapes,
                                     donate)
        first = fn.first_call_on(self.name)
        span = "first_call" if first else "dispatch"
        clock = self._phases
        t0 = _now()
        if clock is not None:
            clock.push(span, t0, cls=chunk[0][0].task_class.name, n=n)
        try:
            outs = fn(*flat)
        except Exception as exc:
            if any(donate):
                # donation-specific failures (backend aliasing rules)
                # must not cost the whole batched path: retry this
                # dispatch undonated before giving up on the spec
                try:
                    donate = tuple(False for _ in donate)
                    fn = cached_stacked_callable(
                        spec, n, nargs, static, shapes, donate)
                    first = fn.first_call_on(self.name) or first
                    outs = fn(*flat)
                    self.stats["donate_retries"] += 1
                    plog.warning("donated dispatch of %s failed (%s: %s); "
                                 "retried undonated", spec.name,
                                 type(exc).__name__, exc)
                    exc = None
                except Exception as exc2:
                    exc = exc2
            if exc is not None:
                if clock is not None:
                    clock.pop(span)
                self.stats["batch_downgrades"] += 1
                downgrade(spec)
                plog.warning("batched dispatch of %s disabled (%s: %s); "
                             "falling back to per-task", spec.name,
                             type(exc).__name__, exc)
                for task, est, inputs, _ in chunk:
                    self._submit_prepared(es, task, est, inputs)
                return
        t1 = _now()
        if clock is not None:
            clock.pop(span, tasks=n, at_ns=t1)
        dt = t1 - t0
        self._book("dispatch", dt)
        self.stats["dispatch_tasks"] += n
        if first:
            self.stats["first_call_ns"] += dt
            self.stats["first_calls"] += 1
        if fn.reused_by(spec):
            self.stats["program_reuse"] += 1
        self.stats["batches"] += 1
        self.stats["batched_tasks"] += n
        self._note_profile(es, chunk[0][0].task_class.name, dt / 1e3 / n, n)
        if any(donate):
            self.stats["donated"] += sum(donate) * n
        self._finish_submit(es, self._record(chunk, outs, "batched"))

    def _record(self, chunk: List[Tuple], outs: Sequence[Any], how: str,
                waits: Optional[List[Sequence[Any]]] = None) -> _InFlight:
        """The record of one call of ``chunk``'s tasks that returned
        ``outs`` (flat, grouped by output slot)."""
        tasks = [entry[0] for entry in chunk]
        out_flows = [self._out_flows(task) for task in tasks]
        n_out = len(outs) // len(tasks)
        for task, flows in zip(tasks, out_flows):
            assert len(flows) == n_out, (
                f"{task.task_class.name} {how} body returned {n_out} "
                f"arrays for {len(flows)} written flows")
        return _InFlight(tasks, outs, out_flows,
                         sum(entry[1] for entry in chunk), waits)

    # ------------------------------------------------------------------ #
    # set stage-in: the host tiles a chunk of a drained ready set needs  #
    # go to the chip in ONE call, ahead of the per-task stage-in         #
    # ------------------------------------------------------------------ #
    def _host_need(self, task: Task, need: Dict[int, Tuple]) -> Tuple[int, int]:
        """The input tiles of ``task`` that wait for bytes from the
        host (no current copy here, the newest one in host memory):
        their bytes, and the bytes of those not yet in ``need``, which
        they join as id(data) -> (data, the task's stage target, its
        bytes).  A tile whose copy here is the owner (every tile a task
        has written: the common case) costs one ``get_copy`` and one
        compare."""
        index = self.device_index
        OWNED = Coherency.OWNED
        waited = new = 0
        target = None
        for data in self._input_datas((task,)):
            copy = data.get_copy(index)
            if copy is not None and copy.coherency == OWNED:
                continue   # the one newest copy is here
            known = need.get(id(data))
            if known is not None:
                waited += known[2]
                continue
            found = self._host_source(data)
            if found is None:
                continue
            if target is None:
                target = self._stage_target(task)
            nbytes = getattr(found[1].payload, "nbytes", 0)
            need[id(data)] = (data, target, nbytes)
            waited += nbytes
            new += nbytes
        return waited, new

    def _stage_in_set(self, need: Dict[int, Tuple]) -> None:  # holds: self._manager_lock
        """Stage the host tiles ``need`` holds (``_host_need``: those of
        ONE chunk of a drained set) in ONE list ``device_put`` a stage
        target (``prestage_many``), so that the per-task stage-in finds
        them resident.  Semantics never depend on this pass: what it
        leaves (a source on another chip, detached scratch, a lost
        race) the per-task stage-in stages.  Inside the caller's
        ``set_stage`` bracket; counter ``stage_chunks``: the puts."""
        by_target: Dict[Any, List[Data]] = {}
        for data, target, _nbytes in need.values():
            by_target.setdefault(target, []).append(data)
        st = self.stats
        before = st["stage_in_transfers"]
        landing = self._landing
        for target, datas in by_target.items():
            if len(landing) > 1:
                # at most TWO puts in flight: a put of large tiles hands
                # the thread back at 40% of its copy, and puts issued
                # without a bound land late, each behind all the others
                # (PERF.md section 5: two in flight read the copy's own
                # time, none and one 20-50% more).  Small tiles have
                # landed when their put returns: nothing to wait for
                tile = landing.pop(0)
                if not tile.is_deleted():
                    tile.block_until_ready()
            committed = self.prestage_many(datas, target)
            if committed:
                landing.append(
                    committed[-1].get_copy(self.device_index).payload)
        st["stage_chunks"] += st["stage_in_transfers"] - before

    @staticmethod
    def _input_datas(tasks: Sequence[Task]):
        """The Data behind every non-CTL flow of ``tasks`` that carries
        one, in flow order, duplicates included."""
        for task in tasks:
            refs = task.data
            for flow in task.task_class.flows:
                if flow.ctl:
                    continue
                copy_in = refs[flow.flow_index].data_in
                if copy_in is not None and copy_in.data is not None:
                    yield copy_in.data

    def _host_source(self, data: Data) -> Optional[Tuple]:
        """(copy here or None, the newest copy elsewhere, its version)
        if ``data`` has no current copy here and its newest bytes are in
        host memory; None otherwise.  Looked at under the Data's lock,
        the version snapshotted WITH the payload decision: a commit
        must stamp the version these bytes had, not whatever the source
        advanced to meanwhile (an eviction writeback bumping the host
        copy between a device_put and its commit must not get its new
        version pinned onto old bytes)."""
        from ..data.data import is_device_array
        index = self.device_index
        with data._lock:
            copy = data.get_copy(index)
            if copy is not None and copy.coherency != Coherency.INVALID \
                    and copy.version >= data.newest_version():
                return None
            src = data.newest_copy(exclude_device=index)
            src_version = src.version if src is not None else -1
        if src is None or src.payload is None \
                or is_device_array(src.payload):
            return None   # nothing to pull, or the source is a chip
        return copy, src, src_version

    def prestage_data(self, data: Data) -> bool:
        """``prestage_many`` of one Data: was its payload committed?"""
        return bool(self.prestage_many((data,)))

    def prestage_many(self, datas, target=None) -> List[Data]:
        """Stage the newest HOST payload of every Data of ``datas`` that
        has no current copy here, ahead of the stage-in that needs it:
        the one function that issues the host-to-device stage-in of a
        set (a chunk of the manager's drained ready set; the stage
        compiler's prestager).  Plan: a Data whose copy here is the owner (every
        tile a task has written, the common case of a drained set)
        costs one ``get_copy`` and one compare; any other is looked at
        under its lock and, unless current, its newest host copy taken
        with the version those bytes have (``_host_source``).  Transfer: ``_reserve`` once, ONE
        ``jax.device_put`` of the list.  jax walks the list and issues
        a copy per array, so this saves jax's Python a tile and not the
        copy: packing the tiles into one array first (``np.stack``, one
        copy, a jitted split) saves nothing more on the v5e, the host
        copy costs what the calls do (PERF.md section 6, PR 34).
        Commit, under each Data's lock again: SHARED at the snapshotted
        version, unless a racing stage-in got there first (it owns the
        coherency transition; clobbering an OWNED copy or an in-use
        reader would corrupt state), whose hold is undone.  Returns the
        Datas whose payloads committed (resident tiles and lost races
        are excluded, so a caller's hit accounting is exact)."""
        import jax
        if target is None:
            target = self.jax_device
        index = self.device_index
        INVALID, OWNED = Coherency.INVALID, Coherency.OWNED
        # id(data) -> (data, copy-or-None, src, src_version)
        plan: Dict[int, Tuple] = {}
        for data in datas:
            copy = data.get_copy(index)
            if copy is not None and copy.coherency == OWNED:
                continue   # the one newest copy is here
            if id(data) in plan:
                continue
            found = self._host_source(data)
            if found is not None:
                plan[id(data)] = (data,) + found
        if not plan:
            return []
        entries = list(plan.values())
        nbytes = sum(getattr(s.payload, "nbytes", 0)
                     for _d, _c, s, _v in entries)
        self._reserve(nbytes)
        with self._xfer("in", nbytes):
            bufs = jax.device_put(
                [s.payload for _d, _c, s, _v in entries],
                [self._placement(d, target) for d, _c, _s, _v in entries])
        self.stats["stage_in_transfers"] += 1
        self.stats["stage_in_tiles"] += len(entries)
        committed_datas: List[Data] = []
        undo = 0
        for (data, copy, src, src_version), buf in zip(entries, bufs):
            committed = False
            old = 0
            with data._lock:
                if copy is None:
                    copy = data.get_copy(index)
                if copy is None:
                    copy = DataCopy(data, index, payload=None,
                                    dtt=src.dtt)
                    data.attach_copy(copy)
                if copy.readers == 0 and copy.coherency != OWNED \
                        and (copy.coherency == INVALID
                             or copy.version < src_version):
                    old = getattr(copy.payload, "nbytes", 0)
                    copy.payload = buf
                    copy.version = src_version
                    copy.coherency = Coherency.SHARED
                    self._prefetched[id(copy)] = src_version
                    committed = True
            if committed:
                self._account(-old)
                self._lru_touch(copy, owned=False)
                committed_datas.append(data)
            else:   # lost the race: undo this entry's hold
                undo += getattr(src.payload, "nbytes", 0)
        if undo:
            self._account(-undo)
        self.stats["prefetch_issued"] += len(committed_datas)
        self.stats["stage_in_bytes"] += nbytes - undo
        return committed_datas

    def prestaged_current(self, data: Data) -> bool:
        """Is this Data's device copy one WE prestaged and still the
        newest version?  The stage compiler's PRESTAGE_HITS accounting
        (a hit = the fused stage's stage-in will find the buffer
        already resident instead of paying a serial H2D)."""
        with data._lock:
            copy = data.get_copy(self.device_index)
            return (copy is not None
                    and id(copy) in self._prefetched
                    and copy.coherency != Coherency.INVALID
                    and copy.version >= data.newest_version())

    def adopt_output(self, data: Data, arr: Any) -> None:
        """Adopt a device array as ``data``'s newest DEVICE copy — the
        epilog's writeback half without a task (the chain-consume path,
        stagec/chain.py: a rider stage's outputs computed inside an
        earlier pool's chained program land here, staying
        device-resident instead of flushing through host).  The whole
        lookup-attach-commit runs under the data lock (a comm-thread
        prestage of the same tile must not interleave), and the
        adopted copy leaves the prestage set — it was never a
        prefetch, so it must not read as one."""
        with data._lock:
            copy = data.get_copy(self.device_index)
            if copy is None:
                copy = DataCopy(data, self.device_index, payload=None)
                data.attach_copy(copy)
            old = getattr(copy.payload, "nbytes", 0)
            copy.payload = arr
            data.version_bump(self.device_index)
            self._prefetched.pop(id(copy), None)
        self._account(-old)
        self._reserve(getattr(arr, "nbytes", 0))
        self._lru_touch(copy, owned=True)

    def convert(self, payload: Any, dst: Any) -> Any:
        """The reshape engine's conversion of a tile that lives here to
        datatype ``dst``, made here: one program (``jit_CONVERT``), no
        host round trip.  The converted array is the engine's to keep
        and to give back (``release_converted``); no LRU lists it."""
        from ..data.reshape import conversion_program
        nbytes = dst.nbytes
        self._reserve(nbytes)
        out = conversion_program(dst)(payload)
        self.stats["conversions"] += 1
        self.stats["conversion_bytes"] += nbytes
        return out

    def release_converted(self, nbytes: int) -> None:
        """The reshape engine dropped a copy ``convert`` made."""
        self._account(-nbytes)

    def drain(self, context=None) -> None:
        """Retire every remaining window entry, call by call (called
        at wait()-exit: the DAGs are complete, and the records would
        otherwise pin their tasks' object graphs — taskpool,
        collections, copies — until some future taskpool's progress
        happens to run). Async kernel failures in these trailing calls
        are RECORDED on the context, once per call, so the caller's
        raise_pending_error surfaces them instead of a
        silently-successful wait().

        Undispatched entries, in ``pending`` or in the manager's
        ``_backlog`` (a chunked set whose pass raised mid-way), are
        DISCARDED: they can only exist here when the DAG aborted
        mid-accumulation (batched dispatch defers the flush), and
        executing them against a poisoned run would be wrong — drop
        their load contribution and let the abort path settle the
        taskpools."""
        if not self._manager_lock.acquire(blocking=True):
            return  # pragma: no cover - Lock.acquire(True) returns True
        try:
            discarded = len(self._backlog)
            for _task, est, _waited in self._backlog:
                self.load_sub(est)
            self._backlog = []
            while True:
                item = self.pending.pop_front()
                if item is None:
                    break
                self.load_sub(item[1])
                discarded += 1
            if discarded:
                plog.debug.verbose(2, "tpu drain: discarded %d undispatched "
                                   "task(s) of an aborted DAG", discarded)
            for rec in self._window:
                self._retire(rec, context=context)
            self._window = []
            self._window_tasks = 0
            self._prefetched.clear()
            self._landing = []
            self._note_wait()
        finally:
            self._manager_lock.release()

    def _note_wait(self) -> None:  # holds: self._manager_lock
        """The observation behind ``_stage_bound``, taken once a
        ``wait()``, at its exit: what the always-on brackets moved by
        since the wait before: ``chip_wait`` (the manager blocked on
        its chip) and the five it WORKS in, less ``first_call_ns`` (a
        program's first call traces, lowers and loads inside
        ``dispatch``: tens of seconds in a cold call, which would read
        as "the manager is the bound" whatever the chip did).  A wait
        that retired no call leaves the last reading as it is.  One
        reading a wait: a call's sets are all treated alike, and
        nothing is computed a record or a task.  Under
        ``CHIP_WAIT_SHARE`` the chip was not what the call waited for,
        and the next wait's sets are cut only over
        ``STAGE_WHOLE_FACTOR`` times ``STAGE_CHUNK_BYTES``."""
        st = self.stats
        calls, waited, worked = mark = (
            st["retired_calls"], st["chip_wait_ns"],
            sum(st[b + "_ns"] for b in BRACKETS if b != "chip_wait")
            - st["first_call_ns"])
        calls0, waited0, worked0 = self._wait_mark
        self._wait_mark = mark
        if calls != calls0:
            waited, worked = waited - waited0, worked - worked0
            self.wait_reading = (waited, worked)
            self._stage_bound = STAGE_CHUNK_BYTES * (
                STAGE_WHOLE_FACTOR if waited < CHIP_WAIT_SHARE * worked
                else 1)

    def _retire(self, rec: _InFlight, es=None, context=None) -> None:
        """Release one call's window entry: drop its load contribution
        (the sum over its tasks), wait for it ONCE (an executable's
        outputs become ready together) and surface any async kernel
        error once — against a task of the call that DISPATCHED it (es
        or context present: recorded as a task error; teardown:
        logged).  The wait, and nothing else here, is the always-on
        bracket and the phase ``chip_wait``: the manager blocked on the
        device (the eager window's backpressure, a drain), not running
        Python."""
        self.load_sub(rec.est)
        self.stats["retired_calls"] += 1
        failed = None
        clock = self._phases
        t0 = _now()
        if clock is not None:
            clock.push("chip_wait", t0, cls=rec.tasks[0].task_class.name,
                       n=len(rec.tasks))
        try:
            for a in rec.live():
                a.block_until_ready()
        except Exception as exc:
            failed = exc
        t1 = _now()
        if clock is not None:
            clock.pop("chip_wait", at_ns=t1)
        self._book("chip_wait", t1 - t0)
        if failed is not None:
            ctx = context if context is not None else \
                (es.context if es is not None else None)
            if ctx is not None:
                ctx.record_task_error(failed, rec.tasks[0])
            else:
                plog.warning("async kernel of %s (a call of %d) failed "
                             "at drain: %s", rec.tasks[0].snprintf(),
                             len(rec.tasks), failed)
        obs = self._obs
        if obs is not None and obs.tracker is not None and es is not None:
            # the device-busy interval for the live overlap gauge:
            # [submit, poll-bracketed completion estimate] when the
            # poll loop stamped one, [submit, now] when this retire
            # itself waited for readiness. Drain/teardown retires
            # (es=None) are skipped — their retire time says nothing
            # about when the kernel finished.
            obs.tracker.note("compute", rec.t0,
                             rec.done_est or time.monotonic_ns())

    def _epilog(self, es, rec: _InFlight) -> None:
        """The epilog of one call (ref: parsec_cuda_kernel_epilog,
        device_cuda_module.c:2365-2430): ONE pass over its tasks
        installs the written copies and releases the readers, one
        ``_account`` takes the summed delta; then the tasks complete in
        dispatch order and what they made ready is handed to the
        scheduler once.  Two always-on brackets: ``epilog`` up to
        ``complete_executions``, ``complete`` around it."""
        from ..runtime.scheduling import complete_executions
        tasks = rec.tasks
        n = len(tasks)
        clock = self._phases
        t0 = _now()
        if clock is not None:
            clock.push("epilog", t0, cls=tasks[0].task_class.name, n=n)
        outs = rec.outs
        index = self.device_index
        # the outputs of one slot share shape and dtype across the
        # call's tasks, and mostly replace a payload of the same:
        # nbytes (a Python property on a jax array) is read once per
        # slot, and of a replaced payload only when it differs
        slots = [(getattr(a, "shape", None), getattr(a, "dtype", None),
                  getattr(a, "nbytes", 0)) for a in outs[::n]]
        delta = scratch = 0
        for i, task in enumerate(tasks):
            for k, fidx in enumerate(rec.out_flows[i]):
                ref = task.data[fidx]
                data = ref.data_in.data if ref.data_in is not None else None
                if data is not None:
                    copy = data.get_copy(index)
                    old = copy.payload
                    copy.payload = outs[k * n + i]
                    shape, dtype, nbytes = slots[k]
                    if getattr(old, "shape", None) != shape \
                            or getattr(old, "dtype", None) != dtype:
                        delta += nbytes - getattr(old, "nbytes", 0)
                    data.version_bump(index)
                    ref.data_out = copy
                else:
                    # nobody's Data (a WRITE-only flow's buffer): the
                    # output is the copy, its readers hold it alive
                    ref.data_in.payload = outs[k * n + i]
                    ref.data_in.version += 1
                    ref.data_in.device_id = index
                    scratch += slots[k][2]
            for flow in task.task_class.flows:
                if task.access_of(flow) == FlowAccess.READ and not flow.ctl:
                    ref = task.data[flow.flow_index]
                    if ref.data_in is not None \
                            and ref.data_in.data is not None:
                        ref.data_in.data.release_reader(index)
        if delta:
            self._account(delta)
        if scratch:
            self.stats["scratch_out_bytes"] += scratch
        self.executed_tasks += n
        t1 = _now()
        if clock is not None:
            clock.pop("epilog", at_ns=t1)
        self._book("epilog", t1 - t0)
        try:
            complete_executions(es, tasks)
        finally:
            self._book("complete", _now() - t1)

    # ------------------------------------------------------------------ #
    # memory management: accounting arena + LRU eviction                 #
    # ------------------------------------------------------------------ #
    def _account(self, delta: int) -> None:
        with self._mem_lock:
            self.mem_used = max(0, self.mem_used + delta)
            if self.mem_used > self.mem_highwater:
                self.mem_highwater = self.mem_used

    def _reserve(self, nbytes: int) -> None:
        """ref: parsec_gpu_data_reserve_device_space w/ LRU eviction and
        cycling guard (device_cuda_module.c:864-1040)."""
        with self._mem_lock:
            self.mem_used += nbytes
            if self.mem_used > self.mem_highwater:
                self.mem_highwater = self.mem_used
            if self.mem_used <= self.mem_budget:
                return
            # evict clean copies first
            for key in list(self._lru_clean):
                if self.mem_used <= self.mem_budget:
                    break
                copy = self._lru_clean.pop(key)
                if not self._evict(copy, writeback=False):
                    self._lru_clean[key] = copy  # in use: keep tracked
            # then dirty (owned) copies with writeback
            for key in list(self._lru_owned):
                if self.mem_used <= self.mem_budget:
                    break
                copy = self._lru_owned.pop(key)
                if not self._evict(copy, writeback=True):
                    self._lru_owned[key] = copy

    def _evict(self, copy: DataCopy, writeback: bool) -> bool:  # holds: self._mem_lock
        """Returns True when the copy was evicted (False: keep it listed)."""
        if copy.payload is None or copy.data is None:
            return True
        if copy.readers > 0:
            return False  # in use; cycling guard keeps it resident
        import numpy as np
        data = copy.data
        if getattr(copy.payload, "is_deleted", lambda: False)():
            # donated to an in-flight batched call: the buffer is gone
            # and the NEW version lands at that task's epilog — drop
            # our accounting reference without touching the payload
            writeback = False
        if writeback and copy.coherency == Coherency.OWNED:
            host = data.get_copy(0)
            if host is not None:
                # np.array (not asarray): jax arrays view as READ-ONLY numpy
                with self._xfer("out", getattr(copy.payload, "nbytes", 0)):
                    host.payload = np.array(copy.payload)
                host.version = copy.version
                host.coherency = Coherency.OWNED
                data.owner_device = 0
                self.stats["stage_out_bytes"] += getattr(host.payload, "nbytes", 0)
        self.mem_used = max(0, self.mem_used - getattr(copy.payload, "nbytes", 0))
        copy.payload = None
        copy.coherency = Coherency.INVALID
        self.stats["evictions"] += 1
        return True

    def _lru_touch(self, copy: DataCopy, owned: bool) -> None:
        key = id(copy)
        with self._mem_lock:
            self._lru_clean.pop(key, None)
            self._lru_owned.pop(key, None)
            (self._lru_owned if owned else self._lru_clean)[key] = copy

    # ------------------------------------------------------------------ #
    # explicit transfers (used by DSLs for flush / pushout)              #
    # ------------------------------------------------------------------ #
    def pull_to_host(self, data: Data) -> Any:
        """D2H writeback of this device's copy if it owns the newest version
        (ref: parsec_cuda_kernel_pop D2H for pushout flows)."""
        import numpy as np
        copy = data.get_copy(self.device_index)
        if copy is None or copy.payload is None:
            return None
        host = data.get_copy(0)
        # np.array (not asarray): numpy views of jax arrays are READ-ONLY,
        # and host bodies mutate the pulled payload in place
        with self._xfer("out", getattr(copy.payload, "nbytes", 0)):
            arr = np.array(copy.payload)
        if host is None:
            host = DataCopy(data, 0, payload=arr)
            data.attach_copy(host)
        else:
            host.payload = arr
        host.version = copy.version
        host.coherency = Coherency.SHARED
        copy.coherency = Coherency.SHARED
        self.stats["stage_out_bytes"] += arr.nbytes
        return arr

    def data_advise(self, data: Data, advice: str) -> None:
        if advice == "prefetch":
            self.prestage_data(data)
        elif advice == "preferred_device":
            data.preferred_device = self.device_index

    def fini(self) -> None:  # lock: exempt(teardown: workers joined, managers quiesced)
        for rec in self._window:
            self._retire(rec)  # teardown: must finalize every device
        self._window.clear()
        self._window_tasks = 0
        self._prefetched.clear()
        self._landing = []


def parse_mesh_shape(shape: Any) -> Tuple[int, int]:
    """``device_mesh_shape`` grammar: "PxQ" grid or a bare chip count
    (a 1 x N row). Empty / "1" / "1x1" means no mesh."""
    s = str(shape or "").strip().lower()
    if not s:
        return (1, 1)
    if "x" in s:
        p, q = s.split("x", 1)
        return (max(1, int(p)), max(1, int(q)))
    return (1, max(1, int(s)))


class _MeshDispatchFailed(Exception):
    """Phase-1 (assemble/trace/dispatch) failure of a mesh-sharded
    batch: nothing was submitted, so the single-chip stacked path can
    safely retry the whole chunk."""


class JaxMeshDevice(JaxDevice):
    """One rank owning a MESH of chips instead of a single jax.Device
    (ISSUE 6 tentpole; the distribute-the-tiles shape of arxiv
    2112.09017).

    - **Placement**: each tile lives on ONE chip of the mesh, chosen
      block-cyclically from its collection coordinates
      (``mesh_position_of``; keyless data round-robins), and STAYS
      there — the resident device copy is chip-pinned.
    - **Intra-mesh dependencies**: a task executes on its home chip
      (the placement of its first written tile); inputs resident on
      other chips hop chip-to-chip (``jax.device_put``, ICI on real
      hardware — counted in ``collective_bytes``) instead of
      serialize -> wire -> deserialize through remote_dep.
    - **Sharded batched dispatch**: a flush group whose size divides
      the chip count compiles through ``shard_map`` over the mesh
      (devices/batching.build_sharded_callable): ONE jitted call
      executes the batch spread across the chips, each chip running
      its slot-block of per-example subgraphs (equal to the
      single-chip stacked path to rounding in ``unroll`` mode).
    - **Fallback semantics**: groups that do not divide the chip count
      and classes whose sharded trace fails (``spec.mesh_ok`` cleared,
      counted in ``mesh_downgrades``) fall back to the single-chip
      stacked path (rows colocated on one chip), and below that to
      per-task dispatch — semantics are never at risk.  Buffer
      donation is forced off in mesh mode (donated global assembly
      does not compose with chip-pinned residency).
    """

    def __init__(self, device_index: int, chips: List[Any],
                 grid: Tuple[int, int]) -> None:
        from ..parallel.mesh import make_mesh
        gp, gq = grid
        assert gp * gq == len(chips), (grid, len(chips))
        super().__init__(device_index, chips[0])
        self.grid = (gp, gq)
        self.mesh = make_mesh(sizes={"tp": gp, "sp": gq},
                              devices=list(chips))
        # row-major over the (gp, gq) grid — the mesh's flat device
        # order, which is also the sharded batch's slot-block order
        self.chips = list(self.mesh.devices.flat)
        self._chip_pos = {d: i for i, d in enumerate(self.chips)}
        plat = getattr(chips[0], "platform", "tpu")
        self.name = f"{plat}:mesh{gp}x{gq}"
        # HBM accounting spans every chip of the mesh
        self.mem_budget *= len(self.chips)
        self.stats.update({"mesh_dispatches": 0, "mesh_tasks": 0,
                           "mesh_moves": 0, "collective_bytes": 0,
                           # sharded -> single-chip stacked
                           "mesh_downgrades": 0})
        self.donate = False   # see class docstring: forced off on mesh
        # per-progress-cycle memo of transient chip hops: the same tile
        # read by several same-flush tasks homed on one chip moves once
        self._move_cache: Dict[Tuple[int, int], Any] = {}
        # jitted gather/scatter helpers for sharded dispatch: ONE call
        # per chip instead of per-row eager ops (an eager slice/stack
        # costs ~1 ms of dispatch each on CPU-jax; jit amortizes)
        self._stack_kerns: Dict[Tuple[int, int], Any] = {}
        self._unbind_kerns: Dict[Tuple[int, int], Any] = {}

    @property
    def mesh_shards(self) -> int:
        """Chips in this device's mesh (obs gauge MESH_SHARDS)."""
        return len(self.chips)

    # ------------------------------------------------------------------ #
    # placement: tile coordinate -> chip                                 #
    # ------------------------------------------------------------------ #
    def _chip_of(self, data: Optional[Data]) -> Any:
        if data is None:
            return self.chips[0]
        coll = getattr(data, "collection", None)
        coords = getattr(data, "mesh_coords", None)
        gp, gq = self.grid
        if coll is not None and coords is not None \
                and hasattr(coll, "mesh_position_of"):
            pr, pc = coll.mesh_position_of(*coords, self.grid)
            return self.chips[(int(pr) % gp) * gq + (int(pc) % gq)]
        hint = getattr(data, "mesh_hint", None)
        if hint is None:
            try:
                hint = hash(data.key)
            except TypeError:
                hint = id(data)
        return self.chips[int(hint) % len(self.chips)]

    def _stage_target(self, task: Task) -> Any:
        """A task's home chip: where its first written tile is placed
        (owner-computes one level below the rank grid); read-only
        tasks run where their first input lives."""
        first = None
        for flow in task.task_class.flows:
            if flow.ctl:
                continue
            ref = task.data[flow.flow_index]
            if ref.data_in is None:
                continue
            data = ref.data_in.data
            if data is None:
                continue
            if first is None:
                first = data
            if task.access_of(flow) & FlowAccess.WRITE:
                return self._chip_of(data)
        return self._chip_of(first)

    def _placement(self, data: Data, target: Any) -> Any:
        """Where a tile's resident device copy lives: coordinate-mapped
        collection tiles pin to their block-cyclic mesh position;
        keyless data (DTD scratch, detached tiles) is FIRST-TOUCH — it
        stays wherever the first touching task's home chip is, so a
        task's private tiles colocate and never hop."""
        coll = getattr(data, "collection", None)
        if coll is not None \
                and getattr(data, "mesh_coords", None) is not None \
                and hasattr(coll, "mesh_position_of"):
            return self._chip_of(data)
        return target

    def _localize(self, payload: Any, target: Any) -> Any:
        return self._move(payload, target)

    def _move(self, arr: Any, target: Any) -> Any:
        """Transient chip-to-chip hop of a device buffer — the
        intra-mesh dependency edge (ICI transfer on hardware). The
        resident copy stays at its placement chip; consumers pull.
        Memoized per progress cycle (sources stay referenced by the
        drained chunk for the cycle, so ids are stable)."""
        dev = _arr_device(arr)
        if dev is None or dev == target:
            return arr
        key = (id(arr), self._chip_pos.get(target, -1))
        hit = self._move_cache.get(key)
        if hit is not None:
            return hit
        import jax
        moved = jax.device_put(arr, target)
        self._move_cache[key] = moved
        self.stats["mesh_moves"] += 1
        self.stats["collective_bytes"] += getattr(arr, "nbytes", 0)
        return moved

    def progress(self, es) -> int:
        n = super().progress(es)
        if self._move_cache:
            self._move_cache.clear()
        return n

    # ------------------------------------------------------------------ #
    # sharded batched dispatch                                           #
    # ------------------------------------------------------------------ #
    def _dispatch_stacked(self, es, spec, static, shapes, donate,
                          chunk: List[Tuple]) -> None:
        """A flush group on the mesh: the sharded program where the
        group divides the chips, else the base's one stacked call on
        the first task's chip."""
        n = len(chunk)
        k = len(self.chips)
        if spec.mesh_ok and spec.batchable and k > 1 and n >= k \
                and n % k == 0:
            try:
                return self._dispatch_sharded(es, spec, static, shapes,
                                              chunk)
            except _MeshDispatchFailed as exc:
                self.stats["mesh_downgrades"] += 1
                spec.mesh_ok = False
                plog.warning(
                    "mesh-sharded dispatch of %s disabled (%s); falling "
                    "back to single-chip stacked dispatch", spec.name,
                    exc.__cause__ or exc)
        # single-chip stacked fallback: colocate the group's rows on
        # the first task's home chip; the base path applies unchanged
        target = self._stage_target(chunk[0][0])
        chunk = [(t, e, inp, tuple(self._move(a, target) for a in ba))
                 for (t, e, inp, ba) in chunk]
        super()._dispatch_stacked(es, spec, static, shapes, donate, chunk)

    def _dispatch_sharded(self, es, spec, static, shapes,
                          chunk: List[Tuple]) -> None:
        """ONE shard_map-compiled jitted call for ``chunk``, spread
        across the mesh: slot-blocks of n/k tasks per chip, tasks
        sorted by home chip so most rows are already resident where
        their slot computes (the rest hop — intra-mesh traffic XLA
        would move anyway)."""
        import jax
        import jax.numpy as jnp
        from .batching import cached_sharded_callable
        n, k = len(chunk), len(self.chips)
        per = n // k
        nargs = len(shapes)
        # phase 1 — fallible: trace/assemble/dispatch. Nothing has been
        # submitted yet, so a failure here retries on the fallback path.
        clock, span = self._phases, None
        try:
            fn = cached_sharded_callable(spec, n, nargs, static, shapes,
                                         self.mesh)
            order = sorted(range(n), key=lambda i: self._chip_pos.get(
                self._stage_target(chunk[i][0]), 0))
            first = fn.first_call_on(self.name)
            span = "first_call" if first else "dispatch"
            t0 = _now()
            if clock is not None:
                clock.push(span, t0, cls=chunk[0][0].task_class.name, n=n)
            # per-chip assembly: ONE jitted stack call per chip builds
            # that chip's shard of every batch arg (rows already
            # resident there stay put; stragglers hop)
            stack = self._stack_kerns.get((per, nargs))
            if stack is None:
                stack = jax.jit(lambda *rows: tuple(
                    jnp.stack(rows[j * per:(j + 1) * per])
                    for j in range(nargs)))
                self._stack_kerns[(per, nargs)] = stack
            blocks = []   # blocks[c][j]: chip c's shard of arg j
            for c, chip in enumerate(self.chips):
                rows = [self._move(chunk[order[c * per + r]][3][j], chip)
                        for j in range(nargs) for r in range(per)]
                blocks.append(stack(*rows))
            gargs = [jax.make_array_from_single_device_arrays(
                (n,) + shapes[j][0], fn.sharding,
                [blocks[c][j] for c in range(k)])
                for j in range(nargs)]
            outs = fn(*gargs)
        except Exception as exc:
            if clock is not None and span is not None:
                clock.pop(span)
            raise _MeshDispatchFailed(
                f"{type(exc).__name__}: {exc}") from exc
        t1 = _now()
        if clock is not None:
            clock.pop(span, tasks=n, at_ns=t1)
        dt = t1 - t0
        self._book("dispatch", dt)
        self.stats["dispatch_tasks"] += n
        if first:
            self.stats["first_call_ns"] += dt
            self.stats["first_calls"] += 1
        if fn.reused_by(spec):
            self.stats["program_reuse"] += 1
        self.stats["batches"] += 1
        self.stats["batched_tasks"] += n
        self.stats["mesh_dispatches"] += 1
        self.stats["mesh_tasks"] += n
        self._note_profile(es, chunk[0][0].task_class.name, dt / 1e3 / n, n)
        # phase 2 — submission: unbind each chip's output shard into
        # per-task rows with ONE jitted call per chip (results never
        # leave the mesh; a failure past this point is a real error,
        # not a retry)
        n_out = fn.n_out
        shards = [sorted(o.addressable_shards,
                         key=lambda s: self._chip_pos[s.device])
                  for o in outs]
        unbind = self._unbind_kerns.get((per, n_out))
        if unbind is None:
            unbind = jax.jit(lambda *bl: tuple(
                b[i] for b in bl for i in range(per)))
            self._unbind_kerns[(per, n_out)] = unbind
        rows_of = [unbind(*[shards[o][c].data for o in range(n_out)])
                   for c in range(k)]   # rows_of[c][o*per + r]
        # ONE record for the call, its tasks in slot order; each chip's
        # rows come from that chip's unbind call and are ready together
        outs = [rows_of[c][o * per + r] for o in range(n_out)
                for c in range(k) for r in range(per)]
        self._finish_submit(es, self._record(
            [chunk[i] for i in order], outs, "mesh-batched", rows_of))

    def drain(self, context=None) -> None:
        super().drain(context)
        self._move_cache.clear()

    def fini(self) -> None:
        super().fini()
        self._move_cache.clear()


def tpu_chore_hook(device_selector=None):
    """The TPU chore hook: pick an attached tpu device, hand off
    (ref: the generated CUDA hook, jdf2c.c:6557-6904). One dispatch path
    for all accelerator types — see devices/template.template_chore_hook."""
    from .template import template_chore_hook
    return template_chore_hook("tpu", device_selector=device_selector)
