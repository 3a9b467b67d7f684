"""Device time per scan turn of the faulty body's ``rosella.retry_select``
stage (the stale-ghost sweep, the earliest-deadline retry lexsort and its
scatters), in microseconds (``bench/recovery_stages.py``)."""
from bench import recovery_stages


def read(ctx):
    return recovery_stages.stage_us(ctx, "retry_select")
