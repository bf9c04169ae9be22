"""Device module: host seconds per factorization of the device managers
outside submissions and stage-in: draining the ready queue, grouping,
polling in-flight work (``manager``) and the one pass per device CALL
that installs the call's written copies and releases its readers, up to
``complete_executions`` (``epilog``); self time, all threads.  The
manager's wait for the chip in ``_retire`` is the phase ``chip_wait``
since PR 35 and is NOT in this number."""
from perfbench import spans


def read(obs):
    return spans.phase_seconds(obs, ("manager", "epilog"))
