"""Device: wall seconds per untraced factorization the device managers
were blocked waiting for a call's outputs (``_retire``: the eager
window's backpressure and the drain at the end of the call): the
always-on bracket ``chip_wait`` (``perfbench/calls.py``).  The chip
holding the host back, not host work.  None where the program leaves no
such record."""
from perfbench import calls


def read(obs):
    return calls.untraced_seconds(obs, "chip_wait")
