"""Share of the traced window in which no op runs on the device while the chunk
driver is enqueueing the program: the device's idle intervals intersected with
the ``rosella.launch`` spans (``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.idle_phase_share(ctx, "launch")
