"""Data movement: GB per call copied from host memory to a chip for a
runtime-made buffer that is nobody's ``Data`` (counter
``scratch_stage_in_bytes``, all devices): a WRITE-only flow's buffer is
never staged in (the body's output is its first value), so this is 0
unless a host body made a buffer a device task reads.  A count, so a
rehearsal shows it.  None where the program has no such counter."""
from perfbench import counters

COUNT = True


def read(obs):
    moved = counters.per_call(obs, "scratch_stage_in_bytes")
    return None if moved is None else moved / 1e9
