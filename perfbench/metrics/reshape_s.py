"""Data movement: host seconds per factorization in the reshape pass
(phase ``reshape`` of ``obs.phases``, ``parsec:reshape`` in the trace):
where a produced tile's successors declare a datatype, the look-up of
the shared promise and, once a (tile, type), the dispatch of the
conversion program; self time over all threads of the traced calls.
None where the program has no such phase."""
from perfbench import spans


def read(obs):
    try:
        from parsec_tpu.obs import phases
    except ImportError:
        return None
    if "reshape" not in phases.PHASES:
        return None
    return spans.phase_seconds(obs, ("reshape",))
