"""Data movement: GB per call copied from a chip back to the host
(counter ``stage_out_bytes``, all devices): what the DTD flush tasks
pull home (``JaxDevice.pull_to_host``) and what an eviction writes
back.  In the DTD product that is C and nothing else (A and B are read
only and stay where they are).  A count, so a rehearsal shows it.  None
where the program has no such counter."""
from perfbench import counters

COUNT = True


def read(obs):
    moved = counters.per_call(obs, "stage_out_bytes")
    return None if moved is None else moved / 1e9
