"""Task model: task classes, flows, chores, tasks, taskpools.

Reference behavior: ``parsec_task_class_t`` carries in/out flows, parameter
symbols, a priority expression, an ``incarnations`` chore list (one per
device type, each with optional ``evaluate`` + ``hook``), and the generated
lifecycle functions ``prepare_input`` / ``release_deps`` /
``iterate_successors`` (ref: parsec/parsec_internal.h:380-437).
``parsec_taskpool_t`` tracks pending tasks + actions and its termination
detector (ref: parsec/parsec_internal.h:119-161).

TPU-native notes: a chore's hook for device type "tpu" typically wraps a
jax-jit executable; the device module owns stage-in/out and asynchronous
completion (HOOK_RETURN_ASYNC), mirroring the CUDA chore handoff
(SURVEY.md §3.4).
"""
from __future__ import annotations

import itertools
import threading
from enum import IntEnum
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..core.object import Obj
from ..data.data import FlowAccess
from ..data.datarepo import DataRepo
from ..utils import logging as plog


class HookReturn(IntEnum):
    """ref: parsec_hook_return_t"""
    DONE = 0        # body ran, task complete
    ASYNC = 1       # a device/async engine took ownership of completion
    NEXT = 2        # this chore declined; try the next incarnation
    AGAIN = 3       # re-schedule the task later
    DISABLE = 4     # disable this chore for the whole task class
    ERROR = 5


class TaskStatus(IntEnum):
    """ref: parsec_task_status_t parsec/parsec_internal.h:476-481"""
    NONE = 0
    PREPARE_INPUT = 1
    EVAL = 2
    HOOK = 3
    COMPLETE = 4


#: release_deps action masks (ref: PARSEC_ACTION_* parsec/parsec_internal.h)
ACTION_RELEASE_LOCAL_DEPS = 0x1
ACTION_RELEASE_REMOTE_DEPS = 0x2
ACTION_SEND_REMOTE_DEPS = 0x4
ACTION_RELEASE_ALL = 0xFFFF


class Chore:
    """One incarnation of a task class on one device type.

    ref: __parsec_chore_t {type, evaluate, hook} parsec/parsec_internal.h:380-392
    """
    __slots__ = ("device_type", "evaluate", "hook", "dyld_fn", "batch_spec")

    def __init__(self, device_type: str,
                 hook: Callable[["ExecutionStream", "Task"], HookReturn],
                 evaluate: Optional[Callable[["Task"], bool]] = None,
                 dyld_fn: Any = None, batch_spec: Any = None) -> None:
        self.device_type = device_type
        self.hook = hook
        self.evaluate = evaluate
        self.dyld_fn = dyld_fn  # device payload: e.g. the jax callable for tpu
        # batched-dispatch recipe (devices/batching.DeviceBatchSpec):
        # lets the device stack same-class ready tasks into one jitted
        # call; None = per-task dispatch only
        self.batch_spec = batch_spec


class Dep:
    """One dependency edge on a flow (ref: parsec_dep_t).

    ``guard`` decides applicability from the task's locals; ``target`` names
    the peer task class (or None for memory access via the collection);
    ``target_locals`` computes the peer's assignments; ``flow_name`` is the
    peer flow.
    """
    __slots__ = ("target", "flow_name", "guard", "target_locals", "dtt", "ctl")

    def __init__(self, target: Optional[str], flow_name: Optional[str] = None,
                 guard: Optional[Callable[..., bool]] = None,
                 target_locals: Optional[Callable[..., Any]] = None,
                 dtt: Any = None, ctl: bool = False) -> None:
        self.target = target
        self.flow_name = flow_name
        self.guard = guard
        self.target_locals = target_locals
        self.dtt = dtt
        self.ctl = ctl


class Flow:
    """A named data flow of a task class (ref: parsec_flow_t,
    parsec/include/parsec/parsec_description_structures.h:92)."""
    __slots__ = ("name", "access", "flow_index", "deps_in", "deps_out", "ctl")

    def __init__(self, name: str, access: FlowAccess, flow_index: int,
                 deps_in: Optional[List[Dep]] = None,
                 deps_out: Optional[List[Dep]] = None, ctl: bool = False) -> None:
        self.name = name
        self.access = access
        self.flow_index = flow_index
        self.deps_in = deps_in or []
        self.deps_out = deps_out or []
        self.ctl = ctl


class TaskDataRef:
    """Per-flow data binding of one task instance (ref: parsec_data_pair_t)."""
    __slots__ = ("source_repo", "source_repo_key", "data_in", "data_out", "fulfilled")

    def __init__(self) -> None:
        self.source_repo: Optional[DataRepo] = None
        self.source_repo_key: Any = None
        self.data_in = None    # DataCopy consumed
        self.data_out = None   # DataCopy produced
        self.fulfilled = False


class TaskClass:
    """ref: parsec_task_class_t"""

    def __init__(self, name: str, task_class_id: int, nb_flows: int,
                 flows: Optional[List[Flow]] = None,
                 incarnations: Optional[List[Chore]] = None,
                 nb_locals: int = 0,
                 priority_fn: Optional[Callable[["Task"], int]] = None) -> None:
        self.name = name
        self.task_class_id = task_class_id
        self.nb_flows = nb_flows
        self.flows = flows or []
        self.incarnations: List[Chore] = incarnations or []
        self.nb_locals = nb_locals
        self.priority_fn = priority_fn
        self.repo = DataRepo(nb_flows) if nb_flows else None
        # lifecycle hooks; DSLs fill these in
        self.prepare_input: Optional[Callable] = None
        self.prepare_output: Optional[Callable] = None
        self.release_deps: Optional[Callable] = None
        self.iterate_successors: Optional[Callable] = None
        self.iterate_predecessors: Optional[Callable] = None
        self.complete_execution: Optional[Callable] = None
        self.release_task: Optional[Callable] = None
        self.key_fn: Callable[[Tuple], Any] = lambda locals_: locals_
        self.time_estimate: Optional[Callable[["Task", Any], float]] = None

    def chore_mask_all(self) -> int:
        # open-ended: chores appended later (DTD add_chore) stay eligible
        return 0xFFFFFFFF

    def chore_order(self) -> List[int]:
        """Execution preference: accelerator incarnations first (the
        generated code lists the CUDA chore before CPU; ref jdf2c.c:6557)."""
        return sorted(range(len(self.incarnations)),
                      key=lambda i: self.incarnations[i].device_type == "cpu")

    def __repr__(self) -> str:  # pragma: no cover
        return f"<TaskClass {self.name}#{self.task_class_id} flows={self.nb_flows}>"


class Task(Obj):
    """One task instance (ref: parsec_task_t)."""

    __slots__ = ("taskpool", "task_class", "locals", "priority",
                 "base_priority", "status",
                 "chore_mask", "selected_device", "selected_chore", "data",
                 "repo_entry", "body_args", "user", "es_hint", "dtd",
                 "flow_access")

    def __init__(self, taskpool: "Taskpool", task_class: TaskClass,
                 locals_: Tuple = (), priority: int = 0) -> None:
        super().__init__()
        self.taskpool = taskpool
        self.task_class = task_class
        self.locals = locals_
        self.priority = priority
        # the DSL's static priority expression, kept apart from
        # ``priority`` (which the dynamic critical-path profile may
        # re-stamp at every schedule — runtime/profile.py): re-stamping
        # recomputes from this base, so it stays idempotent
        self.base_priority = priority
        self.status = TaskStatus.NONE
        self.chore_mask = task_class.chore_mask_all()
        self.selected_device = None      # devices.Device once placed
        self.selected_chore: Optional[int] = None
        self.data: List[TaskDataRef] = [TaskDataRef() for _ in range(task_class.nb_flows)]
        self.repo_entry = None
        self.body_args: Any = None       # DSL-specific payload (DTD param list)
        self.user: Any = None
        self.es_hint: int = -1
        self.dtd: Any = None             # DTD bookkeeping record
        # per-instance access override (DTD: same body, different modes per
        # insertion; PTG instances inherit the class flows and leave it None)
        self.flow_access: Optional[List[FlowAccess]] = None

    def access_of(self, flow: "Flow") -> FlowAccess:
        if self.flow_access is not None:
            return self.flow_access[flow.flow_index]
        return flow.access

    @property
    def key(self) -> Any:
        return self.task_class.key_fn(self.locals)

    def snprintf(self) -> str:
        args = ", ".join(map(str, self.locals))
        return f"{self.task_class.name}({args})"

    def __repr__(self) -> str:  # pragma: no cover
        return f"<Task {self.snprintf()} prio={self.priority}>"


class Taskpool(Obj):
    """ref: parsec_taskpool_t — a DAG instance submitted to a context."""

    _id_iter = itertools.count(1)

    #: the record of this taskpool as a part of a compound
    #: (runtime/compound.py), which the device module stamps with the
    #: part's first device call; None for a taskpool enqueued alone
    _part: Optional[Dict[str, Any]] = None

    def __init__(self, name: str = "taskpool", nb_task_classes: int = 0) -> None:
        super().__init__()
        self.taskpool_id = next(Taskpool._id_iter)
        self.name = name
        self.context = None
        self.task_classes: List[TaskClass] = []
        self.nb_task_classes = nb_task_classes
        self.devices_index_mask = ~0
        self.priority = 0
        self.tdm = None                   # termination detector, set on enqueue
        self.on_enqueue: Optional[Callable] = None
        self.on_complete: Optional[Callable] = None
        self.startup_hook: Optional[Callable] = None  # (context, tp) -> [ready tasks]
        self._complete_cbs: List[Callable] = []
        # run from abort() (ft/ eviction), NOT on normal termination —
        # observers that charge state per live pool (the serving
        # layer's admission accounting) hook both lists
        self._abort_cbs: List[Callable] = []
        self._lock = threading.Lock()
        self._completed = threading.Event()
        self.aborted = False    # ft/: rank eviction aborted this DAG
        self._finishing = False  # abort/termination claimed (see _claim)
        # lazily-constructed per-taskpool info items (ref: info object
        # arrays hanging off parsec_taskpool_t; torn down on completion)
        from ..core.info import InfoObjectArray, taskpool_infos
        self.info = InfoObjectArray(taskpool_infos, self)

    # -- task accounting (delegated to the termination detector) ------------
    def add_tasks(self, n: int) -> None:
        self.tdm.taskpool_addto_nb_tasks(n)

    def task_completed(self, n: int = 1) -> None:
        self.tdm.taskpool_addto_nb_tasks(-n)

    def add_pending_action(self, n: int = 1) -> None:
        self.tdm.taskpool_addto_runtime_actions(n)

    def pending_action_done(self, n: int = 1) -> None:
        self.tdm.taskpool_addto_runtime_actions(-n)

    def set_nb_tasks(self, n: int) -> None:
        self.tdm.taskpool_set_nb_tasks(n)

    # -- completion ---------------------------------------------------------
    def _claim_finish(self, abort: bool) -> bool:
        """Atomically claim the ONE finish of this pool. An abort (the
        ft/ eviction path, fired from a detector/transport thread) and
        a termdet settle (a worker thread) can race; whoever claims
        first decides whether completion callbacks run — an unlocked
        check-then-act would let callbacks fire on a pool the runtime
        is simultaneously declaring failed."""
        with self._lock:
            if self._finishing:
                return False
            self._finishing = True
            self.aborted = abort
            return True

    def abort(self) -> None:
        """FT eviction path (ft/): the DAG cannot finish (a
        participating rank is gone). Unblock ``wait_completed`` WITHOUT
        running the completion callbacks — the pool did not complete,
        and a waiter must consult the context's recorded errors. The
        dedicated ``_abort_cbs`` DO run, so per-pool charges held by
        observers (serve/ admission) are released either way. A late
        termination_detected (counters settling after the abort) is a
        no-op; losing the claim to a real termination is fine too (the
        pool DID complete — nothing to abort)."""
        if not self._claim_finish(abort=True):
            return
        plog.warning("taskpool %d (%s) aborted (rank eviction)",
                     self.taskpool_id, self.name)
        for cb in self._abort_cbs:
            cb(self)
        ctx = self.context
        self._completed.set()
        if ctx is not None:
            ctx._taskpool_done(self)

    def termination_detected(self) -> None:
        """ref: parsec_taskpool_termination_detected (scheduling.c:212-230)"""
        if not self._claim_finish(abort=False):
            return
        plog.debug.verbose(5, "taskpool %d (%s) terminated", self.taskpool_id, self.name)
        if self.on_complete is not None:
            self.on_complete(self)
        for cb in self._complete_cbs:
            cb(self)
        ctx = self.context
        self._completed.set()
        if ctx is not None:
            ctx._taskpool_done(self)

    def wait_completed(self, timeout: Optional[float] = None) -> bool:
        return self._completed.wait(timeout)

    @property
    def completed(self) -> bool:
        return self._completed.is_set()
