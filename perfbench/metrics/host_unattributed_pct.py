"""What the phase clock cannot see: the share of the root span that the
calling thread spent outside every span (``other``: building the
taskpool, start-up, loop overhead between sites), mean over the traced
factorizations."""
from perfbench import spans


def read(obs):
    records = spans.traced_records(obs)
    if records is None:
        return None
    shares = [rec["by_thread"][rec["caller_thread"]]["other_ns"]
              / (rec["t1_ns"] - rec["t0_ns"]) for rec in records]
    return 100.0 * sum(shares) / len(shares)
