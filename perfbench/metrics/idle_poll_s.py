"""Idle polling: host seconds per factorization that workers with no task
spent in select calls that returned none and in passes over the
engines (less the manager work those passes ran), all threads.  It
holds the interpreter lock the working threads need; ``parked`` (asleep
on the condition variable) does not and is only printed."""
from perfbench import spans


def read(obs):
    return spans.phase_seconds(obs, ("idle_poll",))
