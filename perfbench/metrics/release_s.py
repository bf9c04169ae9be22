"""Dependency release: host seconds per factorization inside
complete_execution and release_deps (self time, so less the schedule
spans they contain), all threads (``parsec_tpu.obs.phases``)."""
from perfbench import spans


def read(obs):
    return spans.phase_seconds(obs, ("complete", "release_deps"))
