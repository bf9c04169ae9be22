"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class UNMQR (``jit_UNMQR_x<n>``, ``jit_UNMQR``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "UNMQR")
