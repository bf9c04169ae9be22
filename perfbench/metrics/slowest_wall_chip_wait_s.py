"""Device: of ``slowest_wall_excess_s``, the seconds the slowest call's
managers waited for the chip beyond the median call's (bracket
``chip_wait``, mean over the managers; ``perfbench/calls.py``)."""
from perfbench import calls


def read(obs):
    return (calls.slowest_wall(obs) or {}).get("chip_wait_s")
