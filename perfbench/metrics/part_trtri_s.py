"""Composition: seconds per factorization from the enqueue of the
composed call's part ``dtrtri_L`` to its completion (its last task
released; ``runtime/compound.py``).  None where the program leaves no
such record."""
from perfbench import compound


def read(obs):
    return compound.part_seconds(obs, "dtrtri_L")
