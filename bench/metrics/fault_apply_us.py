"""Device time per scan turn of the faulty body's ``rosella.fault_apply`` stage
(stall, crash kill and ghosting, deadline timeout), in microseconds
(``bench/recovery_stages.py``)."""
from bench import recovery_stages


def read(ctx):
    return recovery_stages.stage_us(ctx, "fault_apply")
