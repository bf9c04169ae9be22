"""DTD front end: host seconds per factorization the inserting thread
spent inside ``insert_task`` (argument parsing, class lookup, last-user
chaining of the tiles; less the ``schedule`` spans it contains), one
span per insert; self time (``parsec_tpu.obs.phases``).  None where the
program has no such phase or the cell inserts nothing."""
from perfbench import spans


def read(obs):
    return spans.phase_seconds(obs, ("dtd_insert",)) or None
