"""The blocking half of every ``ops`` entry point: enqueue + wait, or
for a DTD entry point enqueue + start + insert + flush + wait."""
from __future__ import annotations

from typing import Any, Callable, Sequence

from ..obs.phases import root_span


def run_blocking(context: Any, op: str, taskpools: Sequence[Any]) -> None:
    """Run ``taskpools`` on ``context`` one after the other, under one
    root span named ``op`` (obs/phases.py).  The call leaves a record
    in ``obs.phases.completed()``: always its stamps and what the
    device managers' always-on brackets moved by (``manager``: a few
    microseconds a call); with a JAX profiler session recording, or
    ``Context(profile=True)``, also where every thread's host time
    went.  The first taskpool's id identifies the request in every
    span."""
    with root_span(context, op, taskpools[0].taskpool_id):
        for tp in taskpools:
            context.add_taskpool(tp)
            context.wait()


def run_inserting(context: Any, op: str, tp: Any,
                  insert: Callable[[Any], None]) -> None:
    """Run the DTD taskpool ``tp`` on ``context`` under one root span
    named ``op``, in the order of DPLASMA's ``testing_*_dtd`` drivers:
    the taskpool added and the context started BEFORE the first insert
    (so tasks run while later ones are inserted), ``insert(tp)`` making
    every ``insert_task`` call, then the flush of every tile back home,
    the taskpool's wait and the context's."""
    with root_span(context, op, tp.taskpool_id):
        context.add_taskpool(tp)
        context.start()
        insert(tp)
        tp.data_flush_all()
        tp.wait()
        context.wait()
