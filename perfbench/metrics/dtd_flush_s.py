"""DTD front end: host seconds per factorization inside the flush tasks'
pull of each tile's newest copy back to its home on the host (waits for
the chip to finish the tile, then copies it), all threads; self time.
None where the program has no such phase."""
from perfbench import spans


def read(obs):
    return spans.phase_seconds(obs, ("dtd_flush",)) or None
