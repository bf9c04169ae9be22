"""Compile: host seconds per factorization inside those builds and
loads (the durations of the backend-compile events)."""


def read(obs):
    if not obs["n_counted"] or "load_s" not in obs["counters"]:
        return None
    return obs["counters"]["load_s"] / obs["n_counted"]
