"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class POTRF (``jit_POTRF_x<n>``, ``jit_POTRF``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "POTRF")
