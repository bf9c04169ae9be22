"""Scheduler: host seconds per factorization inside select (that returned
a task), prepare_input, exec (the chore hook up to the hand-off to the
device module) and schedule (queueing freshly enabled tasks, waking
workers); self time, all threads (``parsec_tpu.obs.phases``)."""
from perfbench import spans


def read(obs):
    return spans.phase_seconds(
        obs, ("select", "prepare_input", "exec", "schedule"))
