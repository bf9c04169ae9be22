"""Device module: host seconds inside device submissions per
factorization (``dispatch_ns``), summed over the device managers."""


def read(obs):
    if not obs["n_counted"] or "dispatch_ns" not in obs["counters"]:
        return None
    return obs["counters"]["dispatch_ns"] / 1e9 / obs["n_counted"]
