"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class TSMQR (``jit_TSMQR_x<n>``, ``jit_TSMQR``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "TSMQR")
