"""Scheduler and dependency release: the window's slowest untraced
factorization less its median untraced one, seconds of wall
(``perfbench/calls.py``).  None with fewer than two untraced calls
with records."""
from perfbench import calls


def read(obs):
    return (calls.slowest_wall(obs) or {}).get("excess_s")
