"""Device time per scan turn of the faulty body's ``rosella.spec_select`` stage
(the backup-copy lexsort and the planner's greedy placement), in
microseconds (``bench/recovery_stages.py``)."""
from bench import recovery_stages


def read(ctx):
    return recovery_stages.stage_us(ctx, "spec_select")
