"""Data movement: chip-to-chip ``device_put`` calls per call of the
entry point that the device managers issued for a stage-in, a tile each
(counter ``peer_pulls`` of ``dev.stats``, all devices:
``JaxDevice._peer_pull``): a collection tile whose newest copy another
chip holds (those bytes are ``stage_in_peer_gb``), and a runtime-made
buffer another chip's task wrote (LU's pivot tile: nobody's ``Data``, so
no copy of it is kept on the reader's chip and every consumer task pulls
it again).  LU on a 1 x 4 grid at NT = 40 expects 756: 114 block columns
(each panel once a chip that reads it), 3 copies of the last panel's
pivot tile, and 639 pulls of a pivot tile by an UPDATE or the next PANEL
on another chip.  A count, so a rehearsal shows it.  None where the
program has no such counter (the parent of the PR that added it)."""
from perfbench import counters

COUNT = True


def read(obs):
    return counters.per_call(obs, "peer_pulls")
