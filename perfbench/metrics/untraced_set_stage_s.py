"""Data movement: wall seconds per untraced factorization the device
managers spent in the set pass (``_stage_in_set``: the look at every
input flow of a drained ready set and its one ``device_put``): the
always-on bracket ``set_stage`` of the window's call records
(``perfbench/calls.py``).  None where the program leaves no such
record."""
from perfbench import calls


def read(obs):
    return calls.untraced_seconds(obs, "set_stage")
