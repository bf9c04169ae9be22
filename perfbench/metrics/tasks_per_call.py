"""Device module: tasks per device submission.  A submission is one
stacked call (``batches``) or one per-task call (``dispatch_tasks`` less
``batched_tasks``)."""
COUNT = True


def read(obs):
    c = obs["counters"]
    if "dispatch_tasks" not in c:
        return None
    calls = c["batches"] + c["dispatch_tasks"] - c["batched_tasks"]
    return c["dispatch_tasks"] / calls if calls else None
