"""The least time the chip could take for a DAG's tile tasks.

For each task class: count x max(flops / peak FLOP/s, bytes / peak
B/s), with the count, flops and bytes formulas of the class's file
under ``kernels/`` and the peaks of ``peaks.json``.  A roofline share
is that least time over the device's busy time in the trace.
"""
from .spec import formula


def least_time(kernels, sizes, peaks):
    """(seconds, per-class rows).  A row is (class, count, least seconds
    of one task, the bound that holds: 'compute' or 'bandwidth')."""
    total, rows = 0.0, []
    for k in kernels:
        count = int(formula(k["count"], sizes))
        t_flops = float(formula(k["flops"], sizes)) / peaks["flops_per_s"]
        t_bytes = float(formula(k["bytes"], sizes)) / peaks["hbm_bytes_per_s"]
        one = max(t_flops, t_bytes)
        rows.append((k["class"], count, one,
                     "compute" if t_flops >= t_bytes else "bandwidth"))
        total += count * one
    return total, rows
