"""Device time per scan turn of the alias-table build from the fresh mu_hat, in
microseconds: the ops whose innermost scope is ``rosella.alias_build``
(``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.stage_us(ctx, "alias_build")
