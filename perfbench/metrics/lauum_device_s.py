"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class LAUUM (``jit_LAUUM_x<n>``, ``jit_LAUUM``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "LAUUM")
