"""PINS — Performance INStrumentation callback framework.

Reference behavior: typed callback sites compiled into the hot path
(SELECT / PREPARE_INPUT / RELEASE_DEPS / EXEC / COMPLETE_EXEC / SCHEDULE
begin/end pairs), with pluggable modules subscribing per event type
(ref: parsec/mca/pins/pins.h:27-52, invoked as PARSEC_PINS(es, EXEC_BEGIN, task)
from parsec/scheduling.c:152,182,447-456). Modules in-tree: task_profiler,
papi, alperf, print_steals, iterators_checker, ptg_to_dtd.

Here the sites are function-call hooks that are near-free when no module is
registered (a module-count fast path).
"""
from __future__ import annotations

import threading
from enum import IntEnum
from typing import Any, Callable, Dict, List


class PinsEvent(IntEnum):
    SELECT_BEGIN = 0
    SELECT_END = 1
    PREPARE_INPUT_BEGIN = 2
    PREPARE_INPUT_END = 3
    RELEASE_DEPS_BEGIN = 4
    RELEASE_DEPS_END = 5
    DATA_FLUSH_BEGIN = 6
    DATA_FLUSH_END = 7
    EXEC_BEGIN = 8
    EXEC_END = 9
    COMPLETE_EXEC_BEGIN = 10
    COMPLETE_EXEC_END = 11
    SCHEDULE_BEGIN = 12
    SCHEDULE_END = 13


_N_EVENTS = len(PinsEvent)
_subscribers: List[List[Callable]] = [[] for _ in range(_N_EVENTS)]
_active = 0
_lock = threading.Lock()


def PINS(es: Any, event: PinsEvent, payload: Any) -> None:
    """The instrumentation site; inlined fast path when inactive."""
    if _active == 0:
        return
    for cb in _subscribers[event]:
        cb(es, event, payload)


def pins_is_active() -> bool:
    return _active > 0


class PinsModule:
    """Base class for PINS modules; override ``events`` + ``callback``."""

    name = "base"
    events: List[PinsEvent] = []

    def enable(self) -> None:
        global _active
        with _lock:
            for ev in self.events:
                _subscribers[ev].append(self.callback)
                _active_incr()

    def disable(self) -> None:
        with _lock:
            for ev in self.events:
                try:
                    _subscribers[ev].remove(self.callback)
                except ValueError:
                    continue
                _active_decr()

    def callback(self, es: Any, event: PinsEvent, payload: Any) -> None:
        raise NotImplementedError


def _active_incr() -> None:
    global _active
    _active += 1


def _active_decr() -> None:
    global _active
    _active -= 1


#: the begin/end pairs, the phase each books under (obs/phases.py) and
#: the prefix of its trace events where it always wrote some
_PAIRS = (
    ("select", PinsEvent.SELECT_BEGIN, PinsEvent.SELECT_END, None),
    ("prepare_input", PinsEvent.PREPARE_INPUT_BEGIN,
     PinsEvent.PREPARE_INPUT_END, "prep:"),
    ("release_deps", PinsEvent.RELEASE_DEPS_BEGIN,
     PinsEvent.RELEASE_DEPS_END, None),
    ("exec", PinsEvent.EXEC_BEGIN, PinsEvent.EXEC_END, "exec:"),
    ("complete", PinsEvent.COMPLETE_EXEC_BEGIN, PinsEvent.COMPLETE_EXEC_END,
     "complete:"),
    ("schedule", PinsEvent.SCHEDULE_BEGIN, PinsEvent.SCHEDULE_END, None),
)
#: by event number: (phase, is the BEGIN of its pair, trace prefix)
_SITE: List[Any] = [None] * _N_EVENTS
for _phase, _begin, _end, _prefix in _PAIRS:
    _SITE[_begin] = (_phase, True, _prefix)
    _SITE[_end] = (_phase, False, _prefix)


class TaskProfilerModule(PinsModule):
    """Turns the begin/end PINS pairs into trace events (EXEC,
    PREPARE_INPUT, COMPLETE_EXEC; ref: pins/task_profiler) and, while a
    root span is open (``clock``: an obs.phases.PhaseClock), into that
    request's phase books: every pair pushes and pops the thread's span
    stack there."""

    name = "task_profiler"
    events = [ev for pair in _PAIRS for ev in pair[1:3]]

    def __init__(self, profile=None, context: Any = None) -> None:
        # profiling.trace.Profile, or None for a module that only feeds
        # the phase clock (a profiler session with no profile=True)
        self.profile = profile
        # PINS sites are process-global but profiles are per-rank: with
        # several in-process SPMD contexts, a context-bound module must
        # ignore the other ranks' events or every profile records every
        # rank's tasks (interleaved B/E pairs corrupt the durations)
        self.context = context
        # optional latency sink (an obs.metrics.ExecTimer): with metrics
        # on, the exec duration feeds the histogram from THIS module's
        # existing hook instead of a second PINS callback per task
        self.exec_timer: Any = None
        # the open root span's phase clock (obs.phases.root_span sets
        # and clears it); None between requests
        self.clock: Any = None

    def callback(self, es: Any, event: PinsEvent, payload: Any) -> None:
        if self.context is not None and es.context is not self.context:
            return
        phase, begin, prefix = _SITE[event]
        clock = self.clock
        if clock is not None:
            if not begin:
                # a select that returned no task was idle polling
                clock.pop(phase, "idle_poll" if payload is None
                          and phase == "select" else None)
            elif not clock.traced or payload is None:
                clock.push(phase)
            elif phase == "schedule":
                clock.push(phase, n=len(payload))
            else:
                clock.push(phase, cls=payload.task_class.name)
        if self.profile is None or prefix is None:
            return
        stream = self.profile.thread_stream(es)
        key = prefix + (payload.task_class.name if payload is not None
                        and hasattr(payload, "task_class") else "runtime")
        timer = self.exec_timer if phase == "exec" else None
        if not begin:
            stream.end(key)
            if timer is not None:
                timer.end(es.th_id)
            return
        if timer is not None:
            timer.begin(es.th_id)
        info = None
        if phase == "exec" and payload is not None:
            info = {"task": payload.snprintf()}
            # a task class may pin extra span context (stagec/runtime:
            # a compiled stage's member list + the wire trace contexts
            # that fed it, so the merged timeline can attribute the
            # fused span to its cross-rank inputs)
            extra = getattr(payload.task_class, "trace_info", None)
            if extra:
                info = {**info, **extra}
        stream.begin(key, info=info)


class IteratorsCheckerModule(PinsModule):
    """Race/correctness checker: re-runs a PTG task's iterate_successors at
    release time and validates (a) every successor instance lies inside its
    class's iteration space and (b) the successor's input dep on that flow
    resolves back to this producer instance (ref: pins/iterators_checker —
    "validates iterate_successors consistency", SURVEY.md §5.1)."""

    name = "iterators_checker"
    events = [PinsEvent.RELEASE_DEPS_BEGIN]

    def __init__(self) -> None:
        self.errors: List[str] = []
        self.checked = 0

    def callback(self, es: Any, event: PinsEvent, task: Any) -> None:
        tc = task.task_class
        if not hasattr(tc, "ast") or not hasattr(tc, "_iterate_successors"):
            return  # PTG-only checker, like the reference
        self.checked += 1

        def check(succ_tc, succ_locals, flow_name, copy, out_idx,
                  edge_types=None):
            # (a) successor locals within its iteration-space ranges
            env = dict(succ_tc.tp.global_env)
            it = iter(succ_locals)
            for ld in succ_tc.ast.locals:
                if ld.range is not None:
                    v = next(it)
                    if v not in ld.range.values(env):
                        self.errors.append(
                            f"{task.snprintf()} -> {succ_tc.name}{succ_locals}"
                            f": local {ld.name}={v} outside its range")
                    env[ld.name] = v
                else:
                    env[ld.name] = ld.expr(env)
            # (b) reciprocal input dep resolves back to the producer
            fl = succ_tc.ast.flow_by_name(flow_name)
            for d in fl.deps_in():
                t = d.resolve(env)
                if t is None or t.kind != "task":
                    continue
                if t.task_class == tc.name:
                    # dep-target args follow the producer's PARAM order;
                    # task.locals is declaration order — translate
                    args = tc.ast.locals_from_param_args(
                        tuple(a(env) for a in t.args))
                    if args == tuple(task.locals):
                        return
            self.errors.append(
                f"{succ_tc.name}{succ_locals}.{flow_name}: no input dep "
                f"resolving back to producer {task.snprintf()}")

        tc._iterate_successors(es, task, check)


class TaskTimeModule(PinsModule):
    """Per-task-class wall + thread-CPU time accumulation — the software
    stand-in for the reference's papi PINS module (hardware counters per
    event, ref: pins/papi; no PMU access from userspace here, so the
    counters are clock-based)."""

    name = "task_time"
    events = [PinsEvent.EXEC_BEGIN, PinsEvent.EXEC_END]

    def __init__(self) -> None:
        import time
        self._time = time
        self._open: Dict[int, tuple] = {}
        self.wall_ns: Dict[str, int] = {}
        self.cpu_ns: Dict[str, int] = {}
        self.count: Dict[str, int] = {}
        self._lock = threading.Lock()

    def callback(self, es: Any, event: PinsEvent, payload: Any) -> None:
        t = self._time
        if event == PinsEvent.EXEC_BEGIN:
            self._open[es.th_id] = (t.monotonic_ns(), t.thread_time_ns())
            return
        opened = self._open.pop(es.th_id, None)
        if opened is None or payload is None:
            return
        name = payload.task_class.name
        dw = t.monotonic_ns() - opened[0]
        dc = t.thread_time_ns() - opened[1]
        with self._lock:
            self.wall_ns[name] = self.wall_ns.get(name, 0) + dw
            self.cpu_ns[name] = self.cpu_ns.get(name, 0) + dc
            self.count[name] = self.count.get(name, 0) + 1


class HWCountersModule(PinsModule):
    """Hardware counters per task via perf_event_open — the pins/papi
    analog (ref: parsec/mca/pins/papi/). One counter set per worker
    thread (opened lazily on that thread, like PAPI's per-ES event
    sets); EXEC begin/end deltas accumulate per task class.

    ``available`` is False when the kernel refuses PMU access
    (perf_event_paranoid, container seccomp) — enable() then no-ops,
    matching a reference build without PAPI."""

    name = "hw_counters"
    events = [PinsEvent.EXEC_BEGIN, PinsEvent.EXEC_END]
    DEFAULT_EVENTS = ["instructions", "cycles", "cache_misses"]

    def __init__(self, counter_names: Any = None) -> None:
        from .perfctr import perf_available
        self.counter_names = list(counter_names or self.DEFAULT_EVENTS)
        self.available = perf_available(self.counter_names)
        self._tls = threading.local()
        self.totals: Dict[str, Dict[str, int]] = {}
        self.count: Dict[str, int] = {}
        self._lock = threading.Lock()

    def enable(self) -> None:
        if not self.available:
            from ..utils import logging as _plog
            _plog.debug.verbose(
                1, "hw_counters: perf_event_open unavailable; disabled")
            return
        super().enable()

    def _set(self):
        s = getattr(self._tls, "set", None)
        if s is False:       # this thread's open already failed: stay off
            return None
        if s is None:
            from .perfctr import PerfCounterSet
            try:
                s = self._tls.set = PerfCounterSet.open(self.counter_names)
            except OSError as exc:
                self._tls.set = False
                # the init-time availability probe can pass and a
                # per-thread open still fail (fd exhaustion, thread-scoped
                # PMU refusal): degrade gracefully — instrumentation must
                # never take down the task execution path
                self.available = False
                from ..utils import logging as _plog
                _plog.debug.verbose(
                    1, "hw_counters: per-thread open failed (%s); disabled",
                    exc)
                return None
        return s

    def callback(self, es: Any, event: PinsEvent, payload: Any) -> None:
        s = self._set()
        if s is None:
            return
        if event == PinsEvent.EXEC_BEGIN:
            self._tls.begin = s.read()
            return
        begin = getattr(self._tls, "begin", None)
        if begin is None or payload is None:
            return
        self._tls.begin = None
        end = s.read()
        name = payload.task_class.name
        with self._lock:
            tot = self.totals.setdefault(
                name, {k: 0 for k in self.counter_names})
            for k, b, e in zip(self.counter_names, begin, end):
                tot[k] += e - b
            self.count[name] = self.count.get(name, 0) + 1

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-class mean counter values (e.g. instructions/task)."""
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            for name, tot in self.totals.items():
                n = max(1, self.count.get(name, 0))
                out[name] = {k: v / n for k, v in tot.items()}
        return out


# discoverable by (framework="pins", name) like the reference's MCA
# component tables (mca_repository.c); out-of-tree modules load by
# dotted path or entry point through the same repository
from ..utils import mca as _mca  # noqa: E402

for _cls in (TaskProfilerModule, IteratorsCheckerModule, TaskTimeModule,
             HWCountersModule):
    _mca.register("pins", _cls.name, _cls)
