"""Share of the traced window in which no op runs on the device while the chunk
driver is pulling the next chunk (the caller's generation included): the
device's idle intervals intersected with the ``rosella.next_chunk`` spans
(``bench/stages.py``)."""
from bench import stages


def read(ctx):
    return stages.idle_phase_share(ctx, "next_chunk")
