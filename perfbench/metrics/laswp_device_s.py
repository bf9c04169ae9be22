"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class LASWP (``jit_LASWP_x<n>``, ``jit_LASWP``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "LASWP")
