"""Composition: host seconds per factorization between one part's
completion and the next part's first device call, summed over the
boundaries of the composed call (``runtime/compound.py``): no device has
anything queued then.  What a fused JDF that pipelines the stages would
remove.  None where the program leaves no such record."""
from perfbench import compound


def read(obs):
    return compound.gap_seconds(obs)
