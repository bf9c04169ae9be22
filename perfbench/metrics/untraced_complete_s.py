"""Scheduler and dependency release: wall seconds per untraced
factorization the device managers spent in ``complete_executions``:
each task's completion, the release of its dependencies and the one
hand-over to the scheduler; the always-on bracket ``complete``
(``perfbench/calls.py``).  None where the program leaves no such
record."""
from perfbench import calls


def read(obs):
    return calls.untraced_seconds(obs, "complete")
