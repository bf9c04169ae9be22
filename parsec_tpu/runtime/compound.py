"""Compound taskpools: run several taskpools sequentially as one.

Reference behavior: ``parsec_compose(start, next)`` chains two taskpools
into a compound whose parts execute one after the other; composing onto an
existing compound appends (ref: parsec/compound.c:13-30). The compound
itself holds no tasks — it enqueues part i+1 from part i's completion
callback and terminates after the last part.

Each part leaves a record (``CompoundTaskpool.records``): its name and,
on the clock of ``obs.phases``, when it was enqueued, when its first
device call left (stamped by the device module, 0 if it made none) and
when it completed.  Between one part's completion and the next part's
first device call no device has anything queued: ``compound_gap_ns``
sums those boundaries.  When the last part ends the records go into the
record of the root span the compound ran under, if any (``obs.phases``),
traced or not.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

from .taskpool import Taskpool

__all__ = ["CompoundTaskpool", "compose", "compound_gap_ns"]


class CompoundTaskpool(Taskpool):
    def __init__(self, parts: List[Taskpool]) -> None:
        super().__init__(name="compound")
        self.parts: List[Taskpool] = list(parts)
        self._idx = 0
        #: one record per part launched so far, in order
        self.records: List[Dict[str, Any]] = []
        self.startup_hook = self._startup

    def _startup(self, context, tp):
        # one pending action keeps the compound alive across the chain
        # (it owns no tasks of its own)
        self.add_pending_action()
        self._launch_next(context)
        return []

    def _launch_next(self, context) -> None:
        if self._idx >= len(self.parts):
            call = context._root_call
            if call is not None:    # the last compound's, if it runs several
                call["parts"] = self.records
                call["compound_gap_ns"] = compound_gap_ns(self.records)
            self.pending_action_done()
            return
        sub = self.parts[self._idx]
        self._idx += 1
        prev_cb = sub.on_complete
        part = sub._part = {"name": sub.name, "enqueued_ns": 0,
                            "first_call_ns": 0, "completed_ns": 0}
        self.records.append(part)

        def chained(done_tp):
            part["completed_ns"] = time.monotonic_ns()
            if prev_cb is not None:
                prev_cb(done_tp)
            self._launch_next(context)

        sub.on_complete = chained
        part["enqueued_ns"] = time.monotonic_ns()
        context.add_taskpool(sub)
        # pools with an explicit end-of-insertion protocol (DTD) must be
        # sealed: nobody calls their blocking wait() inside a chain
        seal = getattr(sub, "seal", None)
        if seal is not None:
            seal()


def compound_gap_ns(records: List[Dict[str, Any]]) -> int:
    """Nanoseconds between each part's completion and the NEXT part's
    first device call, summed over the boundaries both sides of which
    were stamped."""
    return sum(max(0, nxt["first_call_ns"] - prev["completed_ns"])
               for prev, nxt in zip(records, records[1:])
               if prev["completed_ns"] and nxt["first_call_ns"])


def compose(start: Taskpool, next_tp: Taskpool) -> CompoundTaskpool:
    """Chain ``next_tp`` after ``start``; both must not be enqueued yet.
    If ``start`` is already a compound, ``next_tp`` is appended in place
    (ref: parsec_compose appending to an existing compound)."""
    assert start.context is None and next_tp.context is None, \
        "compose() operands must not be enqueued yet"
    if isinstance(start, CompoundTaskpool):
        start.parts.append(next_tp)
        return start
    return CompoundTaskpool([start, next_tp])
