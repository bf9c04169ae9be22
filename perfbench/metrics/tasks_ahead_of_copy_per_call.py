"""Data movement: tasks per call that the device managers dispatched
while host tiles of their own drained ready set were still to be copied
(counter ``tasks_ahead_of_copy`` of ``dev.stats``, all devices:
``JaxDevice._dispatch_ready`` stages a set over 128 MiB of host tiles a
chunk at a time; the tasks that wait for no host tile go first, then
every chunk's tasks right behind its one list ``device_put``; counted
are the first and every chunk but a set's last).  Says that the chunked
pass engages: the chip has those tasks' kernels to run under the rest of
the copy.  0 where every set stays under the bound (small tiles).  With
it in the ``counters`` line: ``stage_chunks``, the list puts the pass
issued.  A count, so a rehearsal shows it.  None where the program has
no such counter (the parent of the PR that added it)."""
from perfbench import counters

COUNT = True


def read(obs):
    return counters.per_call(obs, "tasks_ahead_of_copy")
