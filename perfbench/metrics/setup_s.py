"""End to end: everything before the window: imports, the native core,
the input, init(), the first (compiling or cache-loading) factorization
and one more to warm."""


def read(obs):
    return obs.get("setup_s")
