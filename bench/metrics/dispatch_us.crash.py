"""Device time per scan turn, in the crash cell's faulty body, of the masked
dispatch (probe draws and the jnp PPoT-SQ(2) choice over arrivals and retry
slots), in microseconds: the ops whose innermost scope is
``rosella.dispatch`` (``bench/recovery_stages.py``)."""
from bench import recovery_stages


def read(ctx):
    return recovery_stages.stage_us(ctx, "dispatch")
