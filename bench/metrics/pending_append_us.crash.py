"""Device time per scan turn, in the crash cell's faulty body, of the pending
append (argsort compaction of the 14 in-flight columns and the scatters of
new work), in microseconds: the ops whose innermost scope is
``rosella.pending_append`` (``bench/recovery_stages.py``)."""
from bench import recovery_stages


def read(ctx):
    return recovery_stages.stage_us(ctx, "pending_append")
