"""obs.phases — where the host time of one blocking request went.

One :class:`PhaseClock` per root span (one ``ops.dpotrf`` /
``ops.dgeqrf`` / ... call).  Every instrumentation site that has a
begin and an end — the PINS pairs of ``runtime/scheduling.py`` through
``TaskProfilerModule``, the device module's manager / stage-in /
dispatch / epilog sites, ``Context.park`` and ``progress_engines``, the
DTD front end's insert / window / flush sites —
pushes and pops a per-thread stack here, and the clock books each
span's SELF time (duration less what its child spans cover) under the
span's phase name.  What a thread spent outside every span between the
root span's start and end is its ``other``; so per thread
``sum(self_ns) + other_ns == t1_ns - t0_ns`` exactly.

A clock exists only while someone can read it: a JAX profiler session
is recording when the root span opens (``session_recording``), or the
context was built with ``profile=True``.  Otherwise every site stays on
its ``is None`` fast path, and the call still leaves a record: its two
stamps and its ``manager`` block (below).  Under a
session every span (but ``UNANNOTATED``) is also written into the
profiler's own trace as a ``jax.profiler.TraceAnnotation`` named
``parsec:<phase>``, so the runtime's spans sit on the ``/host:CPU``
lines on the same clock as the device's ``XLA Ops`` lines.

A request that ran a compound taskpool (``runtime/compound.py``: an
``ops.dpoinv`` call is three taskpools in one ``add_taskpool``) also
says, per part, when it was enqueued, when its first device call left
and when it completed (``parts``, ns on this clock), and
``compound_gap_ns``: the time between one part's completion and the
next part's first device call, summed over the boundaries -- no device
has anything queued then.  Not a phase: it is wall time of the request,
not self time of a thread.

The ``manager`` block is in EVERY record, clock or none: the device
module brackets the six places a device manager works (``BRACKETS``;
``devices/tpu.py``) with the wall clock in every run, in ``dev.stats``,
and the record holds what those counters moved by during the call: per
bracket ``wall_ns`` and ``count``, summed over the accelerator devices
(``manager``) and for each (``by_device``).  The brackets of one device
are disjoint, so their sum is at most the call's root span.  Each
``by_device`` entry also holds, under ``reshape``, what the reshape
engine's counters of that device moved by (``RESHAPE_COUNTERS``), and
under ``placement`` the tasks the device ran and which rule of
``get_best_device`` sent them there (``PLACEMENT_COUNTERS``), and under
``stage`` the puts of its chunked set pass, the tasks it sent ahead
of a copy and the sets it left whole (``STAGE_COUNTERS``), and under
``scratch`` the bytes of runtime-made buffers staged in from the host
and written by its tasks (``SCRATCH_COUNTERS``), and under ``peer``
the tiles it pulled from other chips, their bytes and what the pulls
cost its manager's thread (``PEER_COUNTERS``).

Closed root spans leave one record each in a bounded process-wide list
(``completed()``): ``op``, ``id``, ``t0_ns``, ``t1_ns``, ``traced``,
``manager``, ``by_device``, a compound call's ``parts`` and
``compound_gap_ns``, and with a clock ``phases``, ``by_thread`` and
``caller_thread``.  ``format_report(record)`` prints the tables.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

__all__ = ["PHASES", "BRACKETS", "RESHAPE_COUNTERS", "PLACED_BY",
           "PLACEMENT_COUNTERS", "STAGE_COUNTERS", "SCRATCH_COUNTERS",
           "PEER_COUNTERS",
           "PhaseClock", "root_span",
           "session_recording", "completed", "clear_completed",
           "format_report"]

#: every phase a site books under, in the order the report prints them
PHASES = ("select", "idle_poll", "parked", "prepare_input", "exec",
          "schedule", "complete", "release_deps", "reshape", "manager",
          "stage_in",
          "dispatch", "first_call", "chip_wait", "epilog", "dtd_insert",
          "dtd_window", "dtd_flush", "other")

#: the places a device manager works, each bracketed in every run by
#: the device module (``devices/tpu.py``: counters ``<bracket>_ns`` and
#: ``<bracket>_n`` in ``dev.stats``)
BRACKETS = ("set_stage", "group", "dispatch", "chip_wait", "epilog",
            "complete")

#: the reshape engine's always-on counters of a device (``dev.stats``,
#: data/reshape.py): conversions made on the chip and their bytes,
#: lookups an earlier conversion answered, and the wall ns and count of
#: the passes through the engine (``reshape`` is also a phase)
RESHAPE_COUNTERS = ("conversions", "conversion_bytes", "reshape_hits",
                    "reshape_ns", "reshape_n")

#: the counters of a device's ``stats`` that say which rule of
#: ``devices.device.get_best_device`` sent a task there (they add up to
#: the tasks placed among several accelerators), and with the tasks the
#: device ran what a record's ``placement`` holds
PLACED_BY = ("placed_by_owner", "placed_by_advice", "placed_by_load")
PLACEMENT_COUNTERS = ("tasks",) + PLACED_BY

#: what the chunked set pass of a device counts (``devices/tpu.py::
#: _dispatch_ready``): the list puts it issued, one a chunk of a drained
#: ready set, the tasks it dispatched while host tiles of their own set
#: were still to be copied, and the sets over ``STAGE_CHUNK_BYTES`` that
#: went whole because the chip had not made the manager wait; a
#: record's ``stage``
STAGE_COUNTERS = ("stage_chunks", "tasks_ahead_of_copy",
                  "sets_whole_by_wait")

#: runtime-made buffers that are nobody's ``Data`` (a WRITE-only flow's,
#: handed from the task that writes it to its readers; ``devices/tpu.py``):
#: bytes copied to the device from host memory for a stage-in (0 unless
#: a host body made the buffer) and bytes the device's tasks wrote into
#: such buffers; a record's ``scratch``
SCRATCH_COUNTERS = ("scratch_stage_in_bytes", "scratch_out_bytes")

#: what a device pulled from other chips for a stage-in
#: (``devices/tpu.py: _peer_pull``): the chip-to-chip ``device_put``
#: calls, a tile each, the bytes of the ``Data`` tiles among them, and
#: the wall ns of the calls on the manager's thread (inside ``group``);
#: a record's ``peer``
PEER_COUNTERS = ("peer_pulls", "stage_in_peer_bytes", "peer_pull_ns")

#: the counter groups a record's ``by_device`` entry holds beside the
#: brackets, by the key each goes under
_GROUPS = {"reshape": RESHAPE_COUNTERS, "placement": PLACEMENT_COUNTERS,
           "stage": STAGE_COUNTERS, "scratch": SCRATCH_COUNTERS,
           "peer": PEER_COUNTERS}

_now = time.monotonic_ns    # the clock of profiling.trace.ThreadStream
_get_ident = threading.get_ident

#: booked, but not written into the profiler's trace: tens of thousands
#: of microsecond spans a factorization from idle workers, whose
#: annotations would cost more than the spans they describe
UNANNOTATED = frozenset(("select", "idle_poll"))

#: rows of the context's Profile that carry the phase spans, one per
#: thread (above obs.spans' comm / device / health rows)
PHASE_STREAM_TID = (1 << 20) + (1 << 11)

#: a 30 s window of the benchmark makes up to 40 calls after set-up's
#: two; its readers want every one of them
_COMPLETED_MAX = 256
_completed: "deque[Dict[str, Any]]" = deque(maxlen=_COMPLETED_MAX)


def completed() -> List[Dict[str, Any]]:
    """The records of the last (at most ``_COMPLETED_MAX``) closed root
    spans, oldest first."""
    return list(_completed)


def clear_completed() -> None:
    _completed.clear()


def session_recording() -> bool:
    """Is a JAX profiler session recording right now?  ``TraceMe``'s own
    switch (jax 0.9.0): true on every thread from ``start_trace`` (or a
    profiler-server capture) to ``stop_trace``, ~60 ns to ask."""
    from jax._src.lib import _profiler
    return bool(_profiler.TraceMe.is_enabled())


class _Thread:
    """One thread's open spans and booked self times."""

    __slots__ = ("lock", "stack", "phases", "closed", "name", "stream")

    def __init__(self, name: str, stream: Any = None) -> None:
        self.lock = threading.Lock()
        # frames: [phase, start_ns, ns covered by closed children,
        #          TraceAnnotation or None]
        self.stack: List[list] = []
        # phase -> [self_ns, spans, tasks]
        self.phases: Dict[str, List[int]] = {}
        self.closed = False
        self.name = name
        #: this thread's row of the context's Profile, or None
        self.stream = stream

    def book(self, phase: str, self_ns: int, tasks: int) -> None:
        acc = self.phases.get(phase)
        if acc is None:
            acc = self.phases[phase] = [0, 0, 0]
        acc[0] += self_ns
        acc[1] += 1
        acc[2] += tasks


class PhaseClock:
    """The span stack and phase books of one root span."""

    def __init__(self, op: str, ident: int, traced: bool,
                 profile: Any = None) -> None:
        self.op = op
        self.id = ident
        #: a profiler session records: spans go into its trace too
        self.traced = traced
        #: the context's profiling.trace.Profile, or None
        self.profile = profile
        self._threads: Dict[int, _Thread] = {}
        self._new_lock = threading.Lock()
        self._t1 = float("inf")
        self._root_anno = None
        self.caller = threading.get_ident()
        if traced:
            import jax
            self._annotation = jax.profiler.TraceAnnotation
            self._root_anno = self._annotation("parsec:op", op=op, id=ident)
            self._root_anno.__enter__()
        self.t0 = _now()

    def _thread(self) -> _Thread:
        """The calling thread's books (made on its first span)."""
        ident = _get_ident()
        with self._new_lock:
            st = self._threads.get(ident)
            if st is None:
                name = threading.current_thread().name
                if any(t.name == name for t in self._threads.values()):
                    name = f"{name}#{ident}"
                stream = None
                if self.profile is not None:
                    stream = self.profile.stream(
                        PHASE_STREAM_TID + threading.get_native_id(),
                        f"phases:{name}")
                st = self._threads[ident] = _Thread(name, stream)
        return st

    def push(self, phase: str, at_ns: Optional[int] = None,
             **args: Any) -> None:
        """Open a span of ``phase`` on the calling thread, at ``at_ns``
        where the site has read ``_now()`` for a bracket of its own
        (one read feeds both).  ``args`` go into the profiler's trace
        with the span."""
        st = self._threads.get(_get_ident())
        if st is None:
            st = self._thread()
        if st.closed:
            return
        anno = None
        if self.traced and phase not in UNANNOTATED:
            anno = self._annotation("parsec:" + phase, id=self.id, **args)
            anno.__enter__()
        t = _now() if at_ns is None else at_ns
        # only the owner appends; close() cuts what it finds at _t1
        st.stack.append([phase, t if t < self._t1 else self._t1, 0, anno])

    def pop(self, phase: str, booked_as: Optional[str] = None,
            tasks: int = 0, at_ns: Optional[int] = None) -> None:
        """Close the calling thread's innermost open span of ``phase``
        (at ``at_ns``, as ``push`` takes it) and book its self time
        under ``booked_as`` (default: its own name).  Spans left open
        above it (an exception skipped their end) close with it; an end
        with no begin is ignored."""
        t = _now() if at_ns is None else at_ns
        st = self._threads.get(_get_ident())
        if st is None:
            return
        stack = st.stack
        with st.lock:       # against close() booking the same span
            if t > self._t1:
                t = self._t1
            if not stack:
                return
            if stack[-1][0] != phase:
                return self._pop_through(st, phase, t)
            _, start, covered, anno = stack.pop()
            if not st.closed:   # else the root span closed over it
                dur = t - start if t > start else 0
                # _Thread.book, inline: this is the hot path
                acc = st.phases.get(booked_as or phase)
                if acc is None:
                    acc = st.phases[booked_as or phase] = [0, 0, 0]
                acc[0] += dur - covered
                acc[1] += 1
                acc[2] += tasks
                if stack:
                    stack[-1][2] += dur
                if st.stream is not None:
                    st.stream.span("phase:" + (booked_as or phase), start, t)
        if anno is not None:
            anno.__exit__(None, None, None)

    def _pop_through(self, st: _Thread, phase: str, t: int) -> None:  # holds: st.lock
        """The rare end whose begin is not on top: close what an
        exception left open above it, each under its own name."""
        stack = st.stack
        at = len(stack) - 1
        while at >= 0 and stack[at][0] != phase:
            at -= 1
        if at < 0:
            return
        while len(stack) > at:
            name, start, covered, anno = stack.pop()
            if anno is not None:
                anno.__exit__(None, None, None)
            if st.closed:
                continue
            dur = max(0, t - start)
            st.book(name, dur - covered, 0)
            if stack:
                stack[-1][2] += dur
            if st.stream is not None:
                st.stream.span("phase:" + name, start, t)

    def close(self) -> Dict[str, Any]:
        """End the root span: cut every thread's open spans at now,
        freeze the books and return the record."""
        t1 = self._t1 = _now()
        root = t1 - self.t0
        by_thread: Dict[str, Any] = {}
        total: Dict[str, List[int]] = {}
        caller = None
        for ident, st in list(self._threads.items()):
            with st.lock:
                st.closed = True
                covered_above = 0
                for name, start, covered, _anno in reversed(st.stack):
                    dur = max(0, t1 - start)
                    st.book(name, dur - covered - covered_above, 0)
                    covered_above = dur
                phases = {k: list(v) for k, v in st.phases.items()}
            other = root - sum(v[0] for v in phases.values())
            by_thread[st.name] = {
                "phases": {k: _entry(v) for k, v in phases.items()},
                "other_ns": other}
            if ident == self.caller:
                caller = st.name
            phases["other"] = [other, 0, 0]
            for k, v in phases.items():
                acc = total.setdefault(k, [0, 0, 0])
                for i in range(3):
                    acc[i] += v[i]
        if caller is None:      # the caller never entered a span
            caller = threading.current_thread().name
            by_thread[caller] = {"phases": {}, "other_ns": root}
            total.setdefault("other", [0, 0, 0])[0] += root
        if self._root_anno is not None:
            self._root_anno.__exit__(None, None, None)
        return {"op": self.op, "id": self.id, "t0_ns": self.t0,
                "t1_ns": t1, "traced": self.traced,
                "phases": {k: _entry(v) for k, v in total.items()},
                "by_thread": by_thread, "caller_thread": caller}


def _entry(acc: List[int]) -> Dict[str, int]:
    out = {"self_ns": acc[0], "count": acc[1]}
    if acc[2]:
        out["tasks"] = acc[2]
    return out


def _bracket_counters(devices: List[Any]) -> List[tuple]:
    """``(device, {bracket: {field: counter now}})`` of each of
    ``devices`` that keeps the manager's brackets."""
    return [(dev, dict({b: {"wall_ns": dev.stats[b + "_ns"],
                            "count": dev.stats[b + "_n"]}
                        for b in BRACKETS},
                       **{group: {c: dev.stats.get(c, 0) for c in counters}
                          for group, counters in _GROUPS.items()}))
            for dev in devices
            if BRACKETS[0] + "_ns" in getattr(dev, "stats", ())]


def _manager_block(before: List[tuple]) -> Dict[str, Any]:
    """``manager`` and ``by_device`` of a record: what the bracket
    counters moved by since ``before``."""
    after = _bracket_counters([dev for dev, _was in before])
    by_device = [dict({b: {f: now[b][f] - was[b][f] for f in now[b]}
                       for b in BRACKETS + tuple(_GROUPS)},
                      device=dev.name)
                 for (dev, was), (_dev, now) in zip(before, after)]
    return {"manager": {b: {f: sum(e[b][f] for e in by_device)
                            for f in ("wall_ns", "count")}
                        for b in BRACKETS},
            "by_device": by_device}


@contextlib.contextmanager
def root_span(context: Any, op: str, ident: int) -> Iterator[Optional[PhaseClock]]:
    """The root span of one blocking request on ``context``; it leaves
    one record in ``completed()``.  Yields the phase clock, or None
    when nobody could read one (no profiler session recording and no
    ``Context(profile=True)``: the record then holds the stamps and the
    ``manager`` block alone) or when the context is already inside a
    root span (no record)."""
    if context._root_call is not None:
        yield None
        return
    traced = session_recording()
    clock = module = None
    borrowed = False
    if traced or context.profile is not None:
        from ..profiling.pins import TaskProfilerModule
        clock = PhaseClock(op, ident, traced, profile=context.profile)
        module = context._task_profiler
        borrowed = module is None
        if borrowed:    # a session with no profile=True: a module for the call
            module = TaskProfilerModule(None, context=context)
            module.enable()
        module.clock = clock
        context._phase_clock = clock
        for dev in context.devices:
            dev._phases = clock
    # what the call's taskpools leave for its record (a compound's
    # ``parts`` and ``compound_gap_ns``: runtime/compound.py)
    call: Dict[str, Any] = {}
    context._root_call = call
    before = _bracket_counters(context.devices)
    t0 = _now()
    try:
        yield clock
    finally:
        t1 = _now()
        context._root_call = None
        if clock is None:
            record = {"op": op, "id": ident, "t0_ns": t0, "t1_ns": t1,
                      "traced": False}
        else:
            for dev in context.devices:
                dev._phases = None
            context._phase_clock = None
            module.clock = None
            if borrowed:
                module.disable()
            record = clock.close()
        record.update(_manager_block(before))
        record.update(call)
        _completed.append(record)


def format_report(record: Dict[str, Any]) -> str:
    """The tables of one record: with a clock, per phase the self
    seconds summed over threads, the span count, the share of threads x
    root span, then the calling thread's own line; always, per bracket
    of the device managers the wall seconds and the count; then a
    compound call's parts."""
    root = record["t1_ns"] - record["t0_ns"]
    n = len(record.get("by_thread", ()))
    lines = [f"{record['op']} #{record['id']}: root span {root / 1e9:.6f} s, "
             + (f"{n} thread(s), " if n else "no phase clock, ")
             + ("profiler session" if record["traced"] else "no session")]
    if "phases" in record:
        lines.append(f"{'phase':<14}{'self s':>12}{'spans':>10}{'tasks':>8}"
                     f"{'share %':>9}")
        known = [p for p in PHASES if p in record["phases"]]
        for name in known + sorted(set(record["phases"]) - set(known)):
            e = record["phases"][name]
            lines.append(
                f"{name:<14}{e['self_ns'] / 1e9:>12.6f}{e['count']:>10}"
                f"{e.get('tasks', ''):>8}"
                f"{100.0 * e['self_ns'] / (root * n or 1):>9.2f}")
        mine = record["by_thread"][record["caller_thread"]]
        lines.append(f"calling thread {record['caller_thread']}: other "
                     f"{mine['other_ns'] / 1e9:.6f} s "
                     f"({100.0 * mine['other_ns'] / (root or 1):.2f}% of the "
                     f"root span)")
    if record.get("by_device"):
        lines.append(f"{'manager':<14}{'wall s':>12}{'count':>10}"
                     f"{'share %':>17}")
        for name in BRACKETS:
            e = record["manager"][name]
            lines.append(
                f"{name:<14}{e['wall_ns'] / 1e9:>12.6f}{e['count']:>10}"
                f"{100.0 * e['wall_ns'] / (root or 1):>17.2f}")
        managers = len(record["by_device"])
        inside = sum(e["wall_ns"] for e in record["manager"].values())
        lines.append(f"in no bracket: {(root - inside / managers) / 1e9:.6f} "
                     f"s of the root span (the brackets' mean over "
                     f"{managers} manager(s) taken out)")
        chunks, ahead, whole = (sum(e.get("stage", {}).get(c, 0)
                                    for e in record["by_device"])
                                for c in STAGE_COUNTERS)
        if ahead or whole:
            lines.append(f"set pass: {chunks} list puts, {ahead} tasks "
                         f"dispatched ahead of a copy of their own set, "
                         f"{whole} sets over the bound whole (the chip "
                         f"had not made the manager wait)")
        conv = {c: sum(e.get("reshape", {}).get(c, 0)
                       for e in record["by_device"])
                for c in RESHAPE_COUNTERS}
        if conv["reshape_n"]:
            lines.append(
                f"reshape: {conv['conversions']} conversions on the "
                f"device(s), {conv['conversion_bytes']} bytes made, "
                f"{conv['reshape_hits']} look-ups an earlier one answered; "
                f"{conv['reshape_ns'] / 1e9:.6f} s in {conv['reshape_n']} "
                f"passes")
        staged, wrote = (sum(e.get("scratch", {}).get(c, 0)
                             for e in record["by_device"])
                         for c in SCRATCH_COUNTERS)
        if staged or wrote:
            lines.append(f"runtime-made buffers: {wrote} bytes written by "
                         f"tasks, {staged} staged in from the host")
        pulls, pulled, pull_ns = (sum(e.get("peer", {}).get(c, 0)
                                      for e in record["by_device"])
                                  for c in PEER_COUNTERS)
        if pulls:
            lines.append(f"peer pulls: {pulls} tiles from other chips, "
                         f"{pulled} bytes of collection tiles, "
                         f"{pull_ns / 1e9:.6f} s on the managers' threads "
                         f"(inside group)")
    t0 = record["t0_ns"]
    for i, part in enumerate(record.get("parts", ())):
        lines.append(
            f"part {i} {part['name']}: enqueued at "
            f"{(part['enqueued_ns'] - t0) / 1e9:.6f} s, first device call "
            + (f"{(part['first_call_ns'] - t0) / 1e9:.6f}"
               if part["first_call_ns"] else "none")
            + f", completed {(part['completed_ns'] - t0) / 1e9:.6f}")
    if "compound_gap_ns" in record:
        lines.append(f"compound_gap {record['compound_gap_ns'] / 1e9:.6f} s "
                     f"between a part's completion and the next part's "
                     f"first device call")
    return "\n".join(lines)
