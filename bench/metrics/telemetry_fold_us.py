"""Device time per scan turn of the telemetry fold (the turn's observations
folded into the window), in microseconds: the ops whose innermost scope is
``rosella.telemetry_fold`` (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_us(ctx, "telemetry_fold")
