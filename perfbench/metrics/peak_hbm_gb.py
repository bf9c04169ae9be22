"""Device: peak_bytes_in_use after the window on the fullest chip: the
tiles of the matrix being factored, the programs' temporaries, and
whatever the runtime still keeps of factorizations that are over."""


def read(obs):
    peak = obs.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
