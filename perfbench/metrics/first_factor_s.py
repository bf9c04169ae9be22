"""Compile: wall of the first factorization of the process, in set-up.
It compiles every program of the cell, or loads each from the
persistent cache."""


def read(obs):
    return obs.get("first_factor_s")
