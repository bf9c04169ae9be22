"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class GEMMT (``jit_GEMMT_x<n>``, ``jit_GEMMT``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "GEMMT")
