"""Data movement: the part of ``stage_in_gb`` pulled from a copy on
another chip (``stage_in_peer_bytes``, all devices), per factorization."""
COUNT = True


def read(obs):
    if not obs["n_counted"] or "stage_in_peer_bytes" not in obs["counters"]:
        return None
    return obs["counters"]["stage_in_peer_bytes"] / 1e9 / obs["n_counted"]
