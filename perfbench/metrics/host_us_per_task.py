"""Scheduler and dependency release: host microseconds per task, from
outside: the mean factorization wall over the DAG's task count."""


def read(obs):
    if not obs["walls"]:
        return None
    return obs["mean_wall_s"] * 1e6 / obs["n_tasks"]
