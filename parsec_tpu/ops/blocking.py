"""The blocking half of every ``ops`` entry point: enqueue + wait."""
from __future__ import annotations

from typing import Any, Sequence

from ..obs.phases import root_span


def run_blocking(context: Any, op: str, taskpools: Sequence[Any]) -> None:
    """Run ``taskpools`` on ``context`` one after the other, under one
    root span named ``op`` (obs/phases.py): with a JAX profiler session
    recording, or ``Context(profile=True)``, the call leaves a record in
    ``obs.phases.completed()`` saying where its host time went; with
    neither it costs one check.  The first taskpool's id identifies the
    request in every span."""
    with root_span(context, op, taskpools[0].taskpool_id):
        for tp in taskpools:
            context.add_taskpool(tp)
            context.wait()
