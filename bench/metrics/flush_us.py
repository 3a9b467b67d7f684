"""Device time per scan turn of the completion flush (lexsort of the due
completions, gather, clear), in microseconds: the ops whose innermost scope is
``rosella.flush`` (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_us(ctx, "flush")
