"""Device module: wall seconds per untraced factorization the device
managers spent installing the outputs of retired calls and releasing
their readers (``_epilog`` up to ``complete_executions``): the always-on
bracket ``epilog`` (``perfbench/calls.py``).  None where the program
leaves no such record."""
from perfbench import calls


def read(obs):
    return calls.untraced_seconds(obs, "epilog")
