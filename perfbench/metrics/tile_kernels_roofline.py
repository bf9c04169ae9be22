"""Tile kernels: the least time the chip could take for the DAG's tasks
(sum over the operation's kernel classes of count x max(flops / peak,
bytes / bandwidth); ``roofline.least_time``) over the device's busy
time per factorization in the trace (mean over the cell's chips, so on
four chips the least time is a quarter of the one-chip one).  Which
bound holds per class is printed on an earlier line of a traced run."""


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["busy_s"] or not obs["n_traced"]:
        return None
    busy_per_factor = tr["busy_s"] / obs["n_traced"]
    return 100.0 * obs["least_time_s"] / obs["chips"] / busy_per_factor
