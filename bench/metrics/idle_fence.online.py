"""Share of the traced window in which no op runs on the device while the chunk
driver is waiting for the call's outputs: the device's idle intervals
intersected with the ``rosella.fence`` spans (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.idle_phase_share(ctx, "fence")
