"""Device time per scan turn, in the crash cell's faulty body, of the masked
alias-table build from the fresh mu_hat of the up workers, in microseconds:
the ops whose innermost scope is ``rosella.alias_build``
(``bench/recovery_stages.py``)."""
from bench import recovery_stages


def read(ctx):
    return recovery_stages.stage_us(ctx, "alias_build")
