"""Device time per scan turn, in the crash cell's faulty body, of the telemetry
fold (the turn's observations folded into the window), in microseconds: the
ops whose innermost scope is ``rosella.telemetry_fold``
(``bench/recovery_stages.py``)."""
from bench import recovery_stages


def read(ctx):
    return recovery_stages.stage_us(ctx, "telemetry_fold")
