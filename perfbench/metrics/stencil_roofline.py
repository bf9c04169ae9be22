"""Tile kernels: the least time the chip could take for the call's
STENCIL tasks (count x max(flops / peak, bytes / bandwidth) of
``kernels/stencil_1d.STENCIL.json``: the tile read once and written
once, the ghost regions read and written; the bandwidth term binds)
over the device seconds of the class's programs per call
(``stencil_device_s``); ``class_roofline.py``."""
from perfbench import class_roofline


def read(obs):
    return class_roofline.read(obs, "STENCIL")
