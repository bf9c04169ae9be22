"""Tile kernels: device seconds per call inside the programs dispatched
for task class STENCIL (``jit_STENCIL_x<n>``, ``jit_STENCIL``), mean over
the chips."""
from perfbench import spans


def read(obs):
    return spans.class_device_seconds(obs, "STENCIL")
