"""Device: peak_bytes_in_use on the fullest chip after the first
factorization of the process: what one factorization needs, which
decides the largest N that fits 16 GB."""


def read(obs):
    peak = obs.get("memory_peak_first_bytes")
    return peak / 1e9 if peak else None
