"""Device module: wall seconds per untraced factorization inside device
calls: the always-on bracket ``dispatch`` of the window's call records
(``perfbench/calls.py``).  ``dispatch_s`` is the same counter over every
factorization of the window, the traced ones too.  None where the
program leaves no such record."""
from perfbench import calls


def read(obs):
    return calls.untraced_seconds(obs, "dispatch")
