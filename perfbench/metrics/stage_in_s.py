"""Data movement: host seconds per factorization resolving tasks' input
flows to arrays on the chip: ``_stage_in_set`` once per drained ready
set (the look at every input flow of the set and the ONE ``device_put``
of its host tiles, since PR 34), ``_stage_in`` per task (which then
finds those tiles resident), every ``device_put`` either issues; self
time, all threads."""
from perfbench import spans


def read(obs):
    return spans.phase_seconds(obs, ("stage_in",))
