"""Compile: programs built or loaded per factorization inside the
window's timed calls (every jax.monitoring backend-compile event,
whether XLA compiled or the persistent cache answered).  Since PR 25
a stacked program is built once per process for every class that can
say what its body reads, so a window whose set-up met every bucket
reads 0; what is left is a bucket set-up did not meet."""
COUNT = True


def read(obs):
    if not obs["n_counted"] or "loads" not in obs["counters"]:
        return None
    return obs["counters"]["loads"] / obs["n_counted"]
