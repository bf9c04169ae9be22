"""Scheduler, dependency release and stage-in, unsplit: the mean
factorization wall less the host time inside device submissions
(``dispatch_ns``) of the busiest device manager, per factorization.
Timed from outside until the tracing issue splits it."""


def read(obs):
    per_dev = obs["counters_by_device"]
    if not obs["walls"] or not per_dev:
        return None
    busiest = max(d.get("dispatch_ns", 0) for d in per_dev)
    return obs["mean_wall_s"] - busiest / 1e9 / obs["n_counted"]
