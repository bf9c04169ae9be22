"""Scheduler and dependency release (``devices/device.py:
get_best_device``): tasks per call that rule ``placed_by_advice`` placed
among several accelerators:
the tile the task writes was ADVISED to that device
(``data_advise(.., "preferred_device")``) and no accelerator owned it
yet: a first touch the user decided.
The counter of that name in the chosen device's ``stats``, all devices.
The three rules add up to the tasks placed; all read 0 with one
accelerator (nothing to decide).  A count, so a rehearsal shows it.
None where the program has no such counter."""
from perfbench import counters

COUNT = True


def read(obs):
    return counters.per_call(obs, "placed_by_advice")
