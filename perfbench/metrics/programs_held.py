"""Compile: compiled programs the process holds for task classes when
the window has ended (``parsec_tpu.devices.batching.programs_held``:
every signature of every stacked program ``<CLASS>_x<n>`` and of every
kernel named for a lone task, ``<CLASS>``).  One process runs one
operation, so these are the operation's.  A level, read once after the
window: it must not depend on the number of tiles, and a body whose
program depends on a task local shows here as a count that grows with
the DAG.  Nothing where the program has no such function."""
COUNT = True


def read(obs):
    try:
        from parsec_tpu.devices.batching import programs_held
    except ImportError:
        return None
    return programs_held()
