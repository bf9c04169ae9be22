"""Device module: what the spans cost when they are on: the managers'
five working brackets in the window's traced factorizations over the
same in its untraced ones, less one, in percent
(``perfbench/calls.py``).  Every ``program_span`` metric is read from
the traced ones.  None where the program leaves no such record or the
window has no traced call."""
from perfbench import calls


def read(obs):
    return calls.span_inflation_pct(obs)
