"""Device time per scan turn, in the crash cell's faulty body, of the
completion flush (lexsort of the due completions, gather, the response min-
fold, clear), in microseconds: the ops whose innermost scope is
``rosella.flush`` (``bench/recovery_stages.py``)."""
from bench import recovery_stages


def read(ctx):
    return recovery_stages.stage_us(ctx, "flush")
