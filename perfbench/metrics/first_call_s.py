"""Compile: the part of ``dispatch_s`` spent in the first call of each
program on each device manager (``first_call_ns``): trace, lower and
load of this taskpool's own copy, per factorization."""


def read(obs):
    if not obs["n_counted"] or "first_call_ns" not in obs["counters"]:
        return None
    return obs["counters"]["first_call_ns"] / 1e9 / obs["n_counted"]
