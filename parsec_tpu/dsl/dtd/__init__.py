"""DTD — Dynamic Task Discovery front end.

Reference behavior: a sequential task-insertion API that discovers the DAG at
runtime from data access modes (IN/OUT/INOUT + AFFINITY/DONT_TRACK), with
per-tile last-user tracking (WAR/WAW chaining, read-after-read fan-out),
sliding-window backpressure (window 8000 / threshold 4000: over the
window the inserting thread is held, working, until the pool is back
under the threshold), per-taskpool registries of task classes and tiles,
NEW-tile support, accelerator chores via ``add_chore`` (on a class
created ahead of its tasks, or on a body already inserted), and explicit
data flush back home
(ref: parsec/interfaces/dtd/insert_function.c, insert_function.h:284-425,
overlap_strategies.c:1-356, parsec_dtd_data_flush.c:1-397; call stack
SURVEY.md §3.5).

Public surface mirrors the reference:
``DTDTaskpool.insert_task(fn, args...)``, ``create_task_class`` +
``insert_task_with_task_class``, ``tile_of(collection, key)``,
``tile_new(...)``, ``data_flush/data_flush_all``, ``add_chore``, ``wait``.
"""
from __future__ import annotations

import threading
from enum import IntFlag
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...core.hashtable import HashTable
from ...profiling.grapher import grapher
from ...profiling.pins import PINS, PinsEvent
from ...data.data import (Coherency, Data, DataCopy, FlowAccess,
                          data_new_with_payload)
from ...data.datatype import dtt_of_array
from ...runtime.scheduling import (_Backoff, schedule, schedule_keep_best,
                                   task_progress)
from ...runtime.taskpool import (Chore, Flow, HookReturn, Task, TaskClass,
                                 Taskpool)
from ...runtime.termdet import termdet_new
from ...utils import logging as plog
from ...utils.params import params


class AccessMode(IntFlag):
    """ref: parsec_dtd_op_t / flags in insert_function.h"""
    INPUT = 0x1
    OUTPUT = 0x2
    INOUT = 0x3
    VALUE = 0x10         # pass-by-value scalar argument
    SCRATCH = 0x20       # per-task scratch buffer
    REF = 0x40           # opaque reference, no tracking
    AFFINITY = 0x100     # place the task where this tile lives
    DONT_TRACK = 0x200   # do not build dependencies on this argument


INPUT = AccessMode.INPUT
OUTPUT = AccessMode.OUTPUT
INOUT = AccessMode.INOUT
VALUE = AccessMode.VALUE
SCRATCH = AccessMode.SCRATCH
REF = AccessMode.REF
AFFINITY = AccessMode.AFFINITY
DONT_TRACK = AccessMode.DONT_TRACK


class RemoteWriter:
    """SPMD-consistent marker: the tile's last write happened on ``rank``
    and is the ``seq``-th write of the tile."""

    __slots__ = ("rank", "seq")

    def __init__(self, rank: int, seq: int) -> None:
        self.rank = rank
        self.seq = seq


class DTDTile:
    """ref: parsec_dtd_tile_t — tracked unit of data with last-user state.

    Multi-rank fields: ``writers_seq`` counts every write by any rank (the
    insertion stream is SPMD-identical, so the count agrees everywhere);
    ``last_writer`` may be a local record or a RemoteWriter; ``recv_proxy``
    is the local recv-task record materializing a remote write (local-only
    state used for chaining); ``sent_to`` dedups sends of one version.
    """

    __slots__ = ("key", "comm_key", "rank", "data", "home_collection",
                 "last_writer", "readers", "lock", "flushed", "writers_seq",
                 "sent_to", "recv_proxy", "recv_proxy_seq", "flushed_at_seq")

    def __init__(self, key: Any, data: Data, rank: int = 0,
                 home_collection: Any = None, comm_key: Any = None) -> None:
        self.key = key
        self.comm_key = comm_key if comm_key is not None else key
        self.rank = rank
        self.data = data
        self.home_collection = home_collection
        self.last_writer = None      # _DTDRecord | RemoteWriter | None
        self.readers: List["_DTDRecord"] = []
        self.lock = threading.Lock()
        self.flushed = False
        self.writers_seq = 0
        self.sent_to: set = set()
        self.recv_proxy: Optional["_DTDRecord"] = None
        self.recv_proxy_seq = -1
        # SPMD-consistent (set at insertion time); a tile no task has
        # written is at home already and is never flushed
        self.flushed_at_seq = 0


class _DTDRecord:
    """Per-task DTD bookkeeping: dependency counter + successor list."""

    __slots__ = ("task", "deps_remaining", "successors", "completed", "lock")

    def __init__(self, task: Task) -> None:
        self.task = task
        self.deps_remaining = 1   # +1 insertion guard, dropped when fully parsed
        self.successors: List["_DTDRecord"] = []
        self.completed = False
        self.lock = threading.Lock()

    def add_successor(self, succ: "_DTDRecord") -> bool:
        """Register succ; returns False if we already completed (no dep)."""
        with self.lock:
            if self.completed:
                return False
            self.successors.append(succ)
            return True

    def dep_satisfied(self) -> bool:
        with self.lock:
            self.deps_remaining -= 1
            assert self.deps_remaining >= 0
            return self.deps_remaining == 0


class _Param:
    __slots__ = ("value", "mode", "tile", "flow_index")

    def __init__(self, value: Any, mode: AccessMode, tile: Optional[DTDTile],
                 flow_index: int = -1) -> None:
        self.value = value
        self.mode = mode
        self.tile = tile
        self.flow_index = flow_index


def _dtd_cpu_hook(es, task: Task) -> HookReturn:
    """Run the user body; host copies were resolved by prepare_input.

    Materialization happens here (not in prepare_input) so the
    device-chore fallback path is covered too: when an accelerator hook
    returns NEXT and the task lands on this host incarnation, payloads
    that arrived as immutable device arrays (mesh transport data plane,
    or a device-resident newest copy) become writable ndarrays before
    the body runs."""
    for p in task.user or ():
        if p is not None and getattr(p, "tile", None) is not None:
            host = p.tile.data.sync_to_host(es.context.devices)
            Data.materialize_host(host)
    fn = task.task_class.user_body
    rc = fn(es, task)
    return HookReturn.DONE if rc is None else rc


class DTDTaskClass(TaskClass):
    def __init__(self, name: str, tc_id: int, nb_flows: int,
                 body: Callable, flows: List[Flow]) -> None:
        super().__init__(name, tc_id, nb_flows, flows=flows,
                         incarnations=[Chore("cpu", _dtd_cpu_hook)])
        self.user_body = body
        self.prepare_input = _dtd_prepare_input
        self.release_deps = _dtd_release_deps


def _dtd_prepare_input(es, task: Task) -> HookReturn:
    """Resolve data_in copies (ref: data_lookup_of_dtd_task,
    insert_function.c:2014). Accelerator chores stage in themselves; the host
    path must pull the newest version back to the host copy."""
    will_run_on_device = any(
        ch.device_type != "cpu" and (task.chore_mask & (1 << i))
        for i, ch in enumerate(task.task_class.incarnations))
    for flow in task.task_class.flows:
        p: _Param = task.body_args[flow.flow_index]
        if p is None:
            continue
        if p.tile is None:
            continue
        data = p.tile.data
        if will_run_on_device:
            task.data[flow.flow_index].data_in = \
                data.newest_copy() or data.host_copy()
        else:
            task.data[flow.flow_index].data_in = \
                data.sync_to_host(es.context.devices)
        task.data[flow.flow_index].fulfilled = True
    return HookReturn.DONE


def _dtd_flush_prepare_input(es, task: Task) -> HookReturn:
    """prepare_input of the flush class: a flush task takes its tile
    INOUT on the host, so THIS is where the tile's newest copy is
    waited for on the chip and pulled home; on a phase clock that is
    the ``dtd_flush`` span."""
    clock = es.context._phase_clock
    if clock is None:
        return _dtd_prepare_input(es, task)
    clock.push("dtd_flush")
    try:
        return _dtd_prepare_input(es, task)
    finally:
        clock.pop("dtd_flush")


def _dtd_release_deps(es, task: Task, action_mask: int) -> List[Task]:
    """ref: dtd_release_dep_fct (insert_function.c:1603) — mark written
    copies, wake satisfied successors."""
    rec: _DTDRecord = task.dtd
    # version bump for host-written flows (device epilog bumps its own)
    if task.selected_device is None or task.selected_device.device_type == "cpu":
        for flow in task.task_class.flows:
            p: _Param = task.body_args[flow.flow_index]
            if p is not None and p.tile is not None and \
                    (task.access_of(flow) & FlowAccess.WRITE):
                p.tile.data.version_bump(0)
    ready: List[Task] = []
    with rec.lock:
        rec.completed = True
        succs, rec.successors = rec.successors, []
    for s in succs:
        if grapher.enabled:
            grapher.dep(task, s.task.snprintf())
        if s.dep_satisfied():
            ready.append(s.task)
    tp: DTDTaskpool = task.taskpool
    tp._on_task_done(len(ready))
    return ready


class DTDTaskpool(Taskpool):
    """ref: parsec_dtd_taskpool_new (insert_function.c)"""

    MAX_TASK_CLASSES = 25  # ref: insert_function_internal.h:30

    def __init__(self, name: str = "dtd") -> None:
        super().__init__(name=name)
        self.window_size = params.get("dtd_window_size")
        self.threshold_size = params.get("dtd_threshold_size")
        self._task_classes: Dict[Any, DTDTaskClass] = {}
        self._tiles = HashTable()
        self._coll_names: Dict[str, int] = {}
        # the window's books, under _out_lock: tasks inserted and not
        # completed; those of them whose dependencies are met (queued,
        # running or on a device: what can still complete with no
        # further insert); whether the inserting thread sleeps on
        # _out_cond for the pool to come down to the threshold
        self._outstanding = 0
        self._in_flight = 0
        self._held = False
        self._out_lock = threading.Lock()
        self._out_cond = threading.Condition(self._out_lock)
        self._inserted = 0
        # keep-alive action until wait() (so an empty pool doesn't terminate)
        self.tdm = termdet_new(params.get("termdet") if params.get("termdet") != "fourcounter" else "local", self)
        self.tdm.taskpool_addto_runtime_actions(1)
        self._alive = True
        self.comm = None  # remote-dep driver, attached on register
        # inserts before context.add_taskpool are buffered and replayed at
        # enqueue time, so DTD pools compose (parsec_compose chains enqueue
        # parts later) and nest (recursive_call) naturally
        self._pending_inserts: List[tuple] = []
        self._mesh_hint_iter = 0   # insertion-order chip placement hint
        self.on_enqueue = self._replay_pending_inserts

    def _replay_pending_inserts(self, tp) -> None:
        pending, self._pending_inserts = self._pending_inserts, []
        for body, args, kw in pending:
            self.insert_task(body, *args, **kw)

    # ------------------------------------------------------------------ #
    # tiles                                                              #
    # ------------------------------------------------------------------ #
    def tile_of(self, collection, key: Any,
                wire_name: Optional[str] = None) -> DTDTile:
        """ref: parsec_dtd_tile_of (insert_function.h:219) — one DTDTile per
        (collection, key), memoized. The wire key uses the collection *name*
        (or the explicit ``wire_name`` override) so SPMD ranks agree on it
        (per-rank instances of one logical collection must share a name in
        multi-rank runs)."""
        name = wire_name if wire_name is not None else collection.name
        tkey = (id(collection), key)
        # wire keys are (name, key): catch two distinct collections sharing
        # a name before they cross-deliver tile data
        owner = self._coll_names.setdefault(name, id(collection))
        if owner != id(collection):
            raise ValueError(
                f"two collections share the name {name!r}; "
                f"set distinct .name values (the name keys tile messages "
                f"between ranks)")

        def factory() -> DTDTile:
            rank = collection.rank_of_key(key)
            data = collection.data_of_key(key) if rank == self.my_rank \
                else Data(key=("remote", name, key))
            return DTDTile(key, data, rank=rank, home_collection=collection,
                           comm_key=(name, key))
        tile, _ = self._tiles.find_or_insert(tkey, factory)
        return tile

    def tile_of_data(self, data: Data) -> DTDTile:
        tkey = ("data", data.key)

        def factory() -> DTDTile:
            return DTDTile(data.key, data, rank=0)
        tile, _ = self._tiles.find_or_insert(tkey, factory)
        return tile

    def tile_of_array(self, arr: Any, key: Any = None) -> DTDTile:
        """Wrap a host array as a tracked tile.  Keyless tiles get a
        deterministic insertion-order ``mesh_hint`` so a chip-mesh
        device (``device_mesh_shape``) round-robins them across its
        chips in the same order on every run — SPMD-stable placement
        without a collection's coordinate map."""
        data = data_new_with_payload(arr, device_id=0, key=key)
        data.mesh_hint = self._mesh_hint_iter
        self._mesh_hint_iter += 1
        return self.tile_of_data(data)

    def tile_new(self, shape: Tuple[int, ...], dtype=np.float32,
                 key: Any = None) -> DTDTile:
        """ref: NEW-tile support (dtd_test_new_tile) — runtime-allocated."""
        return self.tile_of_array(np.zeros(shape, dtype=dtype), key=key)

    # ------------------------------------------------------------------ #
    # task classes + chores                                              #
    # ------------------------------------------------------------------ #
    def _new_task_class(self, key: Any, name: str, nb_flows: int,
                        body: Callable) -> DTDTaskClass:
        """A class of ``nb_flows`` tracked arguments, found again under
        ``key``: the body of a body-first class, or None for the class
        itself."""
        assert len(self._task_classes) < self.MAX_TASK_CLASSES, \
            "too many DTD task classes (ref limit 25)"
        flows = [Flow(f"flow{i}", FlowAccess.NONE, i) for i in range(nb_flows)]
        tc = DTDTaskClass(name, len(self._task_classes), nb_flows, body, flows)
        self._task_classes[tc if key is None else key] = tc
        self.task_classes.append(tc)
        return tc

    def create_task_class(self, name: str, nb_flows: int,
                          body: Callable) -> DTDTaskClass:
        """ref: parsec_dtd_create_task_class — a class ahead of its
        tasks: ``name``, the count of tracked (tile) arguments its tasks
        take, and the host body.  ``add_chore(tc, ...)`` then gives it
        its accelerator incarnations BEFORE ``insert_task_with_task_class``
        inserts the first task, so no task of the class can reach a
        worker without them (a body-first class gets its chore only
        after its first insert, which races it).  Two classes may share
        a body."""
        return self._new_task_class(None, name, nb_flows, body)

    def _task_class_of(self, body: Any, nb_flows: int,
                       name: Optional[str]) -> DTDTaskClass:
        """The class of an insert: ``body`` is a class of this taskpool
        (class-first), or a host body whose class its first insert
        makes."""
        tc = self._task_classes.get(body)
        if tc is None:
            assert not isinstance(body, DTDTaskClass), \
                f"task class {body.name} belongs to another taskpool"
            tc = self._new_task_class(
                body, name or getattr(body, "__name__", "dtd_task"),
                nb_flows, body)
        assert tc.nb_flows == nb_flows, \
            f"task class {tc.name} inserted with {nb_flows} tracked " \
            f"arguments, it has {tc.nb_flows} flows"
        return tc

    def add_chore(self, body: Any, device_type: str, fn: Any) -> None:
        """ref: parsec_dtd_task_class_add_chore (insert_function.c:2432).
        ``body`` is a class from ``create_task_class``, or a host body
        already inserted once.  ``fn`` for device_type "tpu" is a jax
        callable taking one argument per inserted parameter in insertion
        order — device arrays for tiles, raw Python values for VALUE
        params (same order as unpack_args); it returns arrays for the
        written flows, in order."""
        tc = self._task_classes.get(body)
        assert tc is not None, \
            "add_chore before create_task_class or the first insert_task " \
            "of this body"
        from ...devices.batching import (DeviceBatchSpec, kernel_named_for,
                                         kernel_with_constants, program_name)
        # batched-dispatch recipe (devices/batching.py): tile args are
        # the batch axis, VALUE params are static (part of the group
        # key, so only tasks passing EQUAL values stack together)

        def extract(task: Task, arrays: List[Any]):
            bargs: List[Any] = []
            fidx: List[int] = []
            tmpl: List[Any] = []
            for p in task.user:
                if p.tile is not None:
                    if p.flow_index < 0:
                        return None   # untracked tile: not batchable
                    a = arrays[p.flow_index]
                    if a is None:
                        return None
                    tmpl.append(None)
                    bargs.append(a)
                    fidx.append(p.flow_index)
                elif p.mode & VALUE:
                    try:
                        hash(p.value)
                    except TypeError:
                        return None
                    tmpl.append(("v", p.value))
            return tuple(bargs), tuple(fidx), tuple(tmpl)

        def call(bargs, static):
            it = iter(bargs)
            args = [next(it) if s is None else s[1] for s in static]
            out = fn(*args)
            if out is None:
                return ()
            return tuple(out) if isinstance(out, (tuple, list)) else (out,)

        # a task dispatched alone runs under its class's name too
        # (jit_<CLASS>, as its stacked programs are jit_<CLASS>_x<n>),
        # and a jitted kernel takes its VALUE params as the constants
        # they are in a stacked program
        lone_name = program_name(tc.name, 1)
        alone = kernel_named_for(lone_name, fn)

        def wrapped(task: Task, arrays: List[Any]) -> Any:
            got = extract(task, arrays)
            if got is not None and any(got[2]):
                bound = kernel_with_constants(lone_name, fn, got[2])
                if bound is not None:
                    return bound(*got[0])
            args = [arrays[p.flow_index] if p.tile is not None else p.value
                    for p in task.user
                    if p.tile is not None or (p.mode & VALUE)]
            return alone(*args)

        # cache_token=fn: ``call`` reassembles its args from the static
        # key and invokes only the user kernel, so the compiled stacked
        # callable is taskpool-independent and shared process-wide — a
        # fresh taskpool inserting the same kernel over the same shapes
        # dispatches without retracing
        spec = DeviceBatchSpec(tc.name, extract, call, cache_token=fn)
        from ...devices.tpu import tpu_chore_hook
        tc.incarnations.append(Chore(device_type, tpu_chore_hook(),
                                     dyld_fn=wrapped, batch_spec=spec))

    # ------------------------------------------------------------------ #
    # insertion                                                          #
    # ------------------------------------------------------------------ #
    @property
    def my_rank(self) -> int:
        return self.context.rank if self.context is not None else 0

    @property
    def nb_ranks(self) -> int:
        return self.context.nb_ranks if self.context is not None else 1

    def _task_rank(self, tracked: List[_Param]) -> int:
        """Placement: AFFINITY param's tile rank, else first written tile,
        else first tracked tile (ref: PARSEC_AFFINITY placement)."""
        for p in tracked:
            if p.mode & AFFINITY:
                return p.tile.rank
        for p in tracked:
            if int(p.mode) & 0x2:
                return p.tile.rank
        if tracked:
            return tracked[0].tile.rank
        return 0

    def insert_task_with_task_class(self, tc: DTDTaskClass, *args,
                                    priority: int = 0) -> Optional[Task]:
        """ref: parsec_dtd_insert_task_with_task_class — insert a task of
        a class made by ``create_task_class``; ``args`` as for
        ``insert_task``."""
        return self.insert_task(tc, *args, priority=priority)

    def insert_task(self, body: Any, *args, name: Optional[str] = None,
                    priority: int = 0, _internal: bool = False) -> Optional[Task]:
        """ref: parsec_dtd_insert_task (insert_function.h:284, impl :3506).

        ``args`` are (value, VALUE) / (tile, INPUT|INOUT|OUTPUT [|AFFINITY...])
        pairs, or bare Python values (implicitly VALUE). SPMD: every rank
        inserts every task; only the placement rank executes it — the others
        update tile tracking state and synthesize send tasks for edges
        leaving their rank (ref: remote deps inferred from rank_of,
        SURVEY.md §2.2 DTD row).
        """
        assert self._alive, "insert_task after wait()"
        if self.context is None:
            self._pending_inserts.append(
                (body, args, dict(name=name, priority=priority)))
            return None
        if not _internal:
            self._backpressure()
        clock = self.context._phase_clock
        if clock is None:
            return self._insert(body, args, name, priority)
        # argument parsing, class lookup, last-user chaining; a
        # ``schedule`` inside books under its own name
        clock.push("dtd_insert")
        try:
            return self._insert(body, args, name, priority)
        finally:
            clock.pop("dtd_insert")

    def _insert(self, body: Any, args: Sequence[Any], name: Optional[str],
                priority: int) -> Optional[Task]:
        # parse the vararg list (ref: __parsec_dtd_taskpool_create_task :3219)
        parsed: List[_Param] = []
        flow_count = 0
        for a in args:
            if isinstance(a, tuple) and len(a) == 2 and isinstance(a[1], AccessMode):
                val, mode = a
            else:
                val, mode = a, AccessMode.VALUE
            if mode & (VALUE | REF | SCRATCH) or (mode & DONT_TRACK):
                parsed.append(_Param(val, mode, None))
                continue
            assert isinstance(val, DTDTile), \
                f"tracked argument must be a DTDTile, got {type(val)}"
            p = _Param(val, mode, val, flow_index=flow_count)
            flow_count += 1
            parsed.append(p)
        tracked = [p for p in parsed if p.tile is not None]
        t_rank = self._task_rank(tracked)
        if t_rank != self.my_rank:
            self._process_remote_insertion(tracked, t_rank)
            return None
        return self._insert_local(body, parsed, tracked, name, priority)

    def _insert_local(self, body: Callable, parsed: List[_Param],
                      tracked: List[_Param], name: Optional[str],
                      priority: int, hold_deps: int = 0) -> Task:
        tc = self._task_class_of(body, len(tracked), name)
        task = Task(self, tc, locals_=(self._inserted,), priority=priority)
        self._inserted += 1
        rec = _DTDRecord(task)
        rec.deps_remaining += hold_deps  # comm-gated tasks (recv) hold extra
        task.dtd = rec
        # per-INSTANCE access modes (the same body may be inserted with
        # different modes; the shared class Flow objects stay untouched)
        task.body_args = tracked
        task.user = parsed
        task.flow_access = [FlowAccess(int(p.mode) & 0x3) for p in tracked]
        self.add_tasks(1)
        with self._out_lock:
            self._outstanding += 1

        # dependency discovery from tile last-user state
        # (ref: overlap_strategies.c WAR/fan-out resolution)
        def _chain_after(pred: "_DTDRecord") -> None:
            # take the dep BEFORE publishing rec to the predecessor: if the
            # increment came after add_successor, a concurrently-completing
            # predecessor could consume the insertion guard and schedule a
            # half-built task (then the guard drop would schedule it twice)
            with rec.lock:
                rec.deps_remaining += 1
            if not pred.add_successor(rec):
                rec.dep_satisfied()  # already completed; cannot hit zero here

        for p in tracked:
            tile = p.tile
            acc = int(p.mode) & 0x3
            with tile.lock:
                # only consumers need the remote data materialized; a pure
                # OUTPUT has no RAW dep (and cross-rank WAR/WAW is vacuous)
                local_pred = self._materialize_reader_pred(tile, rec) \
                    if (acc & 0x1) else (tile.last_writer
                                         if isinstance(tile.last_writer, _DTDRecord)
                                         else None)
                if acc == int(AccessMode.INPUT):
                    if local_pred is not None and local_pred is not rec:
                        _chain_after(local_pred)
                    # prune completed readers so read-mostly tiles don't
                    # retain every historical reader record
                    tile.readers = [r for r in tile.readers if not r.completed]
                    tile.readers.append(rec)
                else:  # OUTPUT or INOUT: chain after writer and all readers
                    preds = []
                    if local_pred is not None and local_pred is not rec:
                        preds.append(local_pred)
                    preds.extend(r for r in tile.readers if r is not rec)
                    for pr in preds:
                        _chain_after(pr)
                    tile.writers_seq += 1
                    tile.last_writer = rec
                    tile.recv_proxy = None
                    tile.readers = []
                    tile.sent_to = set()

        # drop the insertion guard; schedule if ready
        if rec.dep_satisfied():
            self._schedule_new(task)
        return task

    def _materialize_reader_pred(self, tile: DTDTile, rec) -> Optional["_DTDRecord"]:
        """The record a local consumer must chain after. A RemoteWriter (or
        remotely-homed pristine tile) is materialized by inserting a
        recv-task whose record becomes the tile's local proxy. Caller holds
        tile.lock."""
        lw = tile.last_writer
        if isinstance(lw, _DTDRecord):
            return lw
        if isinstance(lw, RemoteWriter):
            seq = lw.seq
        elif lw is None and tile.rank != self.my_rank:
            seq = tile.writers_seq  # home data, possibly never written
        else:
            return None  # pristine local tile: no predecessor
        if tile.recv_proxy is not None and tile.recv_proxy_seq == seq:
            return tile.recv_proxy
        proxy = self._insert_recv(tile, seq)
        tile.recv_proxy = proxy
        tile.recv_proxy_seq = seq
        return proxy

    def _insert_recv(self, tile: DTDTile, seq: int) -> "_DTDRecord":
        """Insert the comm-gated recv-task materializing (tile, seq).
        Caller holds tile.lock — the recv chains after current local readers
        manually to avoid re-entering the tracking logic."""
        box: Dict[str, Any] = {}
        task = self._insert_local(
            _dtd_recv_body,
            [_Param(box, VALUE | REF, None), _Param(tile, VALUE | REF, None)],
            [], name="dtd_recv", priority=0, hold_deps=1)
        rec = task.dtd
        # the recv overwrites the tile: order it after live local readers
        for r in tile.readers:
            if not r.completed:
                with rec.lock:
                    rec.deps_remaining += 1
                if not r.add_successor(rec):
                    rec.dep_satisfied()
        tile.readers = []
        assert self.comm is not None, \
            "multi-rank DTD requires a comm engine"
        tp = self

        def on_data(arr):
            box["data"] = arr
            if rec.dep_satisfied():
                tp._schedule_new(task)
        self.comm.dtd_expect(self, tile.comm_key, seq, on_data)
        return rec

    def _process_remote_insertion(self, tracked: List[_Param],
                                  t_rank: int) -> None:
        """A task placed on another rank: emit sends for data leaving my
        rank, update SPMD tile tracking."""
        for p in tracked:
            tile = p.tile
            acc = int(p.mode) & 0x3
            with tile.lock:
                reads = bool(acc & 0x1)
                if reads:
                    lw = tile.last_writer
                    i_hold = isinstance(lw, _DTDRecord) or \
                        (lw is None and tile.rank == self.my_rank)
                    if i_hold and (t_rank, tile.writers_seq) not in tile.sent_to:
                        tile.sent_to.add((t_rank, tile.writers_seq))
                        self._insert_send(tile, tile.writers_seq, t_rank)
                if acc & 0x2:  # the remote task writes a new version
                    tile.writers_seq += 1
                    tile.last_writer = RemoteWriter(t_rank, tile.writers_seq)
                    tile.recv_proxy = None
                    # KEEP live local readers (incl. the send just inserted):
                    # a future recv of the new version chains after them, so
                    # the in-place overwrite of the host payload stays
                    # ordered behind every consumer of the old version
                    tile.readers = [r for r in tile.readers if not r.completed]
                    tile.sent_to = set()

    def _insert_send(self, tile: DTDTile, seq: int, dst: int) -> None:
        """Insert the send-task shipping (tile, seq) to ``dst``. Caller
        holds tile.lock; the send chains after the local writer manually."""
        task = self._insert_local(
            _dtd_send_body,
            [_Param((tile, seq, dst), VALUE | REF, None)],
            [], name="dtd_send", priority=0, hold_deps=1)
        rec = task.dtd
        lw = tile.last_writer
        if isinstance(lw, _DTDRecord) and lw is not rec:
            with rec.lock:
                rec.deps_remaining += 1
            if not lw.add_successor(rec):
                rec.dep_satisfied()
        tile.readers.append(rec)
        # chaining complete: drop the hold (may schedule right away)
        if rec.dep_satisfied():
            self._schedule_new(task)

    def _schedule_new(self, task: Task) -> None:
        ctx = self.context
        assert ctx is not None, "insert_task before context.add_taskpool"
        es = ctx.execution_streams[0]
        with self._out_lock:
            self._in_flight += 1
        schedule(es, [task])

    @staticmethod
    def _next_task(es) -> Optional[Task]:
        """The next task for a thread that helps on stream ``es`` (the
        inserter held by the window, the caller in ``wait``): the bypass
        slot's, else the scheduler's, selected between the worker loop's
        PINS pair (``select`` / ``idle_poll`` on a phase clock)."""
        task = es.next_task
        es.next_task = None
        if task is None:
            PINS(es, PinsEvent.SELECT_BEGIN, None)
            task = es.context.scheduler.select(es)
            PINS(es, PinsEvent.SELECT_END, task)
        return task

    def _on_task_done(self, n_ready: int = 0) -> None:
        """A task completed and made ``n_ready`` successors ready."""
        with self._out_lock:
            self._outstanding -= 1
            self._in_flight += n_ready - 1
            if self._held and self._outstanding <= self.threshold_size:
                self._out_cond.notify_all()

    def _backpressure(self) -> None:
        """ref: parsec_dtd_block_if_threshold_reached (insert_function.c:3215)
        — over the window, the inserting thread does not go back to its
        caller until the pool is at or under the threshold: it runs what
        is ready, drives the engines, and otherwise sleeps until
        completions bring the pool down (a sleep, not a spin: a spin
        would keep the interpreter lock from the threads doing the
        work).  It goes back at once when a task error is pending, and
        when nothing inserted so far can complete without an insert
        (or a message) still to come."""
        if self._outstanding <= self.window_size:
            return
        ctx = self.context
        es = ctx.execution_streams[0]
        clock = ctx._phase_clock
        if clock is not None:
            clock.push("dtd_window")
        ran = misses = 0
        task = None
        try:
            while self._outstanding > self.threshold_size \
                    and not ctx._task_errors:
                task = self._next_task(es)
                if task is not None:
                    task_progress(es, task)
                    ran += 1
                    misses = 0
                elif ctx.progress_engines(es):
                    misses = 0
                elif self._sleep_until_threshold(
                        min(1e-5 * (1 << min(misses, 8)),
                            _Backoff.MAX_SLEEP)):   # the idle backoff's
                    misses += 1
                else:
                    break   # nothing in flight: don't deadlock the inserter
        except Exception as exc:    # a body blew up on this thread:
            ctx.record_task_error(exc, task)    # wait() raises it
        finally:
            if clock is not None:
                clock.pop("dtd_window", tasks=ran)

    def _sleep_until_threshold(self, timeout: float) -> bool:
        """Sleep until completions bring the pool to the threshold, or
        ``timeout`` (the engines and the error list want a look).  False,
        without sleeping, when no inserted task is in flight."""
        with self._out_lock:
            if self._in_flight <= 0:
                return False
            if self._outstanding > self.threshold_size:
                self._held = True
                self._out_cond.wait(timeout)
                self._held = False
        return True

    # ------------------------------------------------------------------ #
    # flush + wait                                                       #
    # ------------------------------------------------------------------ #
    def data_flush(self, tile: DTDTile) -> None:
        """ref: parsec_dtd_data_flush — order a writeback of the tile to its
        home (host copy / collection storage) after its last user. One shared
        task class serves every flush (a per-call closure would exhaust the
        25-class limit). The dedup marker is set at INSERTION time so every
        SPMD rank makes the same decision (an execution-time flag would only
        flip on the home rank and diverge the insertion streams)."""
        if _dtd_flush_body not in self._task_classes:
            self._new_task_class(
                _dtd_flush_body, "dtd_flush", 1, _dtd_flush_body
            ).prepare_input = _dtd_flush_prepare_input
        self.insert_task(_dtd_flush_body, (tile, INOUT | AFFINITY),
                         (tile, VALUE | REF), _internal=True)
        tile.flushed_at_seq = tile.writers_seq

    def data_flush_all(self) -> None:
        """Flush every tile written since its last flush.  A tile that
        was only read gets no flush task: its home copy is the newest,
        and a flush task (INOUT on the host) would bump that copy's
        version under the readers' device copies for nothing."""
        for _, tile in self._tiles.items():
            if tile.flushed_at_seq != tile.writers_seq:
                self.data_flush(tile)

    def seal(self) -> None:
        """No further inserts will come: flush dirty tiles and drop the
        keep-alive so the pool terminates once its tasks finish. Used when
        the pool runs without a blocking ``wait()`` — compound parts and
        recursive sub-pools (compose/recursive_call call this on enqueue)."""
        if not self._alive:
            return
        # flush while still alive: data_flush inserts flush tasks
        self.data_flush_all()
        self._alive = False
        self.tdm.taskpool_addto_runtime_actions(-1)

    def wait(self) -> None:
        """ref: parsec_dtd_taskpool_wait — drop the keep-alive and help
        execute until this taskpool terminates."""
        assert self.context is not None
        if self._alive:
            self._alive = False
            self.tdm.taskpool_addto_runtime_actions(-1)
        ctx = self.context
        ctx.start()
        es = ctx.execution_streams[0]
        backoff = _Backoff()
        while not self.completed and not ctx._task_errors:
            task = self._next_task(es)
            try:
                if task is not None:
                    task_progress(es, task)
                    backoff.hit()
                elif ctx.progress_engines(es):
                    backoff.hit()
                else:
                    backoff.miss(ctx)
            except BaseException as exc:
                ctx.record_task_error(exc, task)
        ctx.raise_pending_error()


def _dtd_flush_body(es, task: Task) -> None:
    """Shared flush task body: the newest copy is back on the host (the
    task's prepare_input pulled it, ``_dtd_flush_prepare_input``)."""
    tile: DTDTile = next(p.value for p in task.user if p.tile is None)
    tile.data.sync_to_host(es.context.devices)
    tile.flushed = True


def _dtd_recv_body(es, task: Task) -> None:
    """Comm-gated recv: materialize the received version into the tile's
    host copy (the task was scheduled only after the data arrived)."""
    box = task.user[0].value
    tile: DTDTile = task.user[1].value
    arr = box["data"]
    host = tile.data.host_copy()
    if host.payload is None:
        host.payload = np.array(arr)
    else:
        np.copyto(host.payload, arr)
    tile.data.version_bump(0)


def _dtd_send_body(es, task: Task) -> None:
    """Ship the tile's current version to the destination rank."""
    tile, seq, dst = task.user[0].value
    tp: DTDTaskpool = task.taskpool
    host = tile.data.sync_to_host(es.context.devices)
    assert host.payload is not None, \
        f"dtd_send of tile {tile.comm_key} with no local payload"
    tp.comm.dtd_send(tp, tile.comm_key, seq, dst, np.asarray(host.payload))


def taskpool_new(name: str = "dtd") -> DTDTaskpool:
    return DTDTaskpool(name=name)


def unpack_args(task: Task) -> List[Any]:
    """ref: parsec_dtd_unpack_args — values for VALUE params, host ndarrays
    for tracked tiles (in the original insertion order)."""
    out: List[Any] = []
    for p in task.user:
        if p.tile is not None:
            host = p.tile.data.get_copy(0)
            if host is None:
                out.append(None)
            else:
                # bodies mutate in place; wire arrivals may be read-only
                # zero-copy views — materialize copies on first write
                out.append(Data.materialize_host(host))
        else:
            out.append(p.value)
    return out
