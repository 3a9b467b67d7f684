"""Share of the traced window in which no op runs on the device while the chunk
driver is copying the chunk's columns to the device: the device's idle
intervals intersected with the ``rosella.h2d`` spans (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.idle_phase_share(ctx, "h2d")
