"""Data movement: bytes staged onto the chips per factorization
(``stage_in_bytes``, all devices; from the host or from another chip)."""
COUNT = True


def read(obs):
    if not obs["n_counted"] or "stage_in_bytes" not in obs["counters"]:
        return None
    return obs["counters"]["stage_in_bytes"] / 1e9 / obs["n_counted"]
