"""Data movement: GB per call that tasks wrote into runtime-made buffers
handed on to their successors (counter ``scratch_out_bytes``, all
devices, bumped where a call's epilog binds the output to the flow's
copy).  In the stencil those are the ghost regions: 2 x 4 R NB bytes a
task; a multiple of that says whole tiles travel.  A count, so a
rehearsal shows it.  None where the program has no such counter."""
from perfbench import counters

COUNT = True


def read(obs):
    moved = counters.per_call(obs, "scratch_out_bytes")
    return None if moved is None else moved / 1e9
