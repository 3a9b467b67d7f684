"""Share of the traced window in which no op runs on the device while the chunk
driver is reading the call's flags and window records back: the device's idle
intervals intersected with the ``rosella.readback`` spans
(``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.idle_phase_share(ctx, "readback")
