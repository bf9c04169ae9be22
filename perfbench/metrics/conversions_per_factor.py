"""Data movement: conversions the reshape engine made on the chips per
factorization (counter ``conversions`` of ``dev.stats``, bumped by
``JaxDevice.convert``: one a (produced tile, target type)).  In the
mixed-precision Cholesky that is the TRSM outputs some lo GEMM reads
(``parsec_tpu.ops.dpotrf_mp.converted_tiles``: 95 at NT = 16 and
band_mid = 5); a multiple of it means a conversion a consumer.  A count,
so a rehearsal shows it.  None where the program has no such counter."""
COUNT = True


def read(obs):
    counters = obs.get("counters") or {}
    if "conversions" not in counters or not obs.get("n_counted"):
        return None
    return counters["conversions"] / obs["n_counted"]
