"""Data movement: host seconds per factorization resolving tasks' input
flows to arrays on the chip (``_stage_in``, the prefetcher, every
``device_put`` they issue); self time, all threads."""
from perfbench import spans


def read(obs):
    return spans.phase_seconds(obs, ("stage_in",))
