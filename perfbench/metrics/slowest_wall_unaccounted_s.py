"""Scheduler and dependency release: of ``slowest_wall_excess_s``, the
seconds found in none of the managers' six brackets: no manager was
working or waiting for the chip (workers parked or not scheduled, the
caller's own thread, the operating system; ``perfbench/calls.py``)."""
from perfbench import calls


def read(obs):
    return (calls.slowest_wall(obs) or {}).get("unaccounted_s")
