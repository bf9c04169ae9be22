"""Compile: programs built or loaded per factorization inside the
window's timed calls (every jax.monitoring backend-compile event,
whether XLA compiled or the persistent cache answered).  The stacked
programs of a PTG taskpool are cached per taskpool, so each
factorization traces, lowers and loads its own again."""
COUNT = True


def read(obs):
    if not obs["n_counted"] or "loads" not in obs["counters"]:
        return None
    return obs["counters"]["loads"] / obs["n_counted"]
