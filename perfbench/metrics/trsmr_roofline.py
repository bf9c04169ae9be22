"""Tile kernels: the least time the chip could take for the DAG's TRSMR
tasks (count x max(flops / peak, bytes / bandwidth) of
``kernels/<operation>.TRSMR.json``: useful work, a triangular tile
counted as its triangle) over the device seconds of the class's programs
per factorization (``trsmr_device_s``); ``class_roofline.py``."""
from perfbench import class_roofline


def read(obs):
    return class_roofline.read(obs, "TRSMR")
