"""Device time per scan turn of the dispatch (probe draws and the PPoT-SQ(2)
kernel), in microseconds: the ops whose innermost scope is ``rosella.dispatch``
(``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_us(ctx, "dispatch")
