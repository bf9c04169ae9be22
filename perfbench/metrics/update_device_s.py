"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class UPDATE (``jit_UPDATE_x<n>``, ``jit_UPDATE``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "UPDATE")
