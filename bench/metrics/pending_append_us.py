"""Device time per scan turn of the pending append (argsort compaction and the
scatters of new work), in microseconds: the ops whose innermost scope is
``rosella.pending_append`` (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_us(ctx, "pending_append")
