"""Tile kernels: device seconds per factorization inside the programs
dispatched for task class GEMMI (``jit_GEMMI_x<n>``, ``jit_GEMMI``), mean
over the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "GEMMI")
