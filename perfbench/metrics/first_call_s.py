"""Compile: the part of ``dispatch_s`` spent in the first call of each
program on each device manager (``first_call_ns``): trace, lower and
load, per factorization.  Since PR 25 a program is built once per
device per process, so this reads 0 in a window unless a bucket is met
there for the first time."""


def read(obs):
    if not obs["n_counted"] or "first_call_ns" not in obs["counters"]:
        return None
    return obs["counters"]["first_call_ns"] / 1e9 / obs["n_counted"]
