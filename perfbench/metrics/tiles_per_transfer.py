"""Data movement: tiles a stage-in transfer carries.  ``stage_in_tiles``
over ``stage_in_transfers``, all devices: every ``device_put`` call the
device module issued to move tiles onto a chip for a stage-in, from the
host or from another chip, counts one transfer; the one call that
carries the host tiles of a drained ready set is one transfer of n.
1.0 where every tile goes alone (a source on another chip, detached
scratch).  Nothing where the program has no such counters."""
COUNT = True


def read(obs):
    c = obs["counters"]
    if not c.get("stage_in_transfers"):
        return None
    return c["stage_in_tiles"] / c["stage_in_transfers"]
