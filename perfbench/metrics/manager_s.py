"""Device module: host seconds per factorization of the device managers
outside submissions and stage-in: draining the ready queue, grouping,
polling in-flight work (``manager``) and retiring finished tasks up to
complete_execution (``epilog``); self time, all threads."""
from perfbench import spans


def read(obs):
    return spans.phase_seconds(obs, ("manager", "epilog"))
