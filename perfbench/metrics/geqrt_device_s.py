"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class GEQRT (``jit_GEQRT_x<n>``, ``jit_GEQRT``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "GEQRT")
