"""Compile: XLA compilations inside the window's timed calls:
jax.monitoring backend-compile events less those the persistent cache
answered; expected 0."""
COUNT = True


def read(obs):
    c = obs["counters"]
    return c["loads"] - c["cache_hits"] if "loads" in c else None
