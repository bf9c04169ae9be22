"""Tile kernels: the least time the chip could take for the DAG's LASWP
tasks (count x max(flops / peak, bytes / bandwidth) of
``kernels/<operation>.LASWP.json``: useful work, the mean task of the
DAG) over the device seconds of the class's programs per factorization
(``laswp_device_s``); ``class_roofline.py``."""
from perfbench import class_roofline


def read(obs):
    return class_roofline.read(obs, "LASWP")
