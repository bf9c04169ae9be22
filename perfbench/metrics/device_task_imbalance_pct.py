"""Scheduler and dependency release (``get_best_device``): how unevenly
a call's tasks fell on the accelerators: (max - mean) / mean of the
tasks a device ran, in percent, the mean over the window's calls.  From
the call records' ``by_device[*]["placement"]["tasks"]``
(``parsec_tpu.obs.phases.completed()``, one record a call, traced or
not).  0 where every device ran the same count.  A count, so a rehearsal
shows it.  None where the program leaves no such record, or with one
accelerator."""
import statistics

from perfbench import calls

COUNT = True


def read(obs):
    got = calls.window_calls(obs)
    if got is None:
        return None
    shares = []
    for rec, _wall in got:
        ran = [e.get("placement", {}).get("tasks")
               for e in rec["by_device"]]
        if len(ran) < 2 or None in ran or not sum(ran):
            return None
        mean = statistics.fmean(ran)
        shares.append(100.0 * (max(ran) - mean) / mean)
    return statistics.fmean(shares)
