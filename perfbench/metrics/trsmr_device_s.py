"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class TRSMR (``jit_TRSMR_x<n>``, ``jit_TRSMR``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "TRSMR")
