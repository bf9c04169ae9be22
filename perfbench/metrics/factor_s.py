"""End to end: seconds per factorization: the timed seconds of all the
window's factorizations over their count, each timed from the
entry-point call to block_until_ready on every tile's newest copy."""


def read(obs):
    return obs["mean_wall_s"] if obs["walls"] else None
