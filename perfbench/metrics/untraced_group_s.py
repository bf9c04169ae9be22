"""Device module: wall seconds per untraced factorization the device
managers spent in the dispatch pass of a drained ready set outside the
set pass, the device calls and the waits for the chip: per-task
stage-in bookkeeping, argument extraction, grouping, filing the
records; the always-on bracket ``group`` (``perfbench/calls.py``).
None where the program leaves no such record."""
from perfbench import calls


def read(obs):
    return calls.untraced_seconds(obs, "group")
