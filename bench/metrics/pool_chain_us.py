"""Device time per scan turn of the replica-pool chain (the inner scan of
dependent submissions), in microseconds: the ops whose innermost scope is
``rosella.pool_chain`` (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_us(ctx, "pool_chain")
