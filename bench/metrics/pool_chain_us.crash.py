"""Device time per scan turn, in the crash cell's faulty body, of the replica-
pool chain over fakes, probe bursts, arrivals, retry and backup copies, in
microseconds: the ops whose innermost scope is ``rosella.pool_chain``
(``bench/recovery_stages.py``)."""
from bench import recovery_stages


def read(ctx):
    return recovery_stages.stage_us(ctx, "pool_chain")
