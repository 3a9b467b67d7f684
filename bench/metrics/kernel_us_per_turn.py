"""Device time of the fused alias PPoT-SQ(2) dispatch kernel per scan turn
(microseconds): the union of its events' intervals in the traced window
over the turns run (one call a turn).  Absent where the trace holds no
such event, or holds it for fewer turns than ran (events were dropped)."""
from bench import kernels


def read(ctx):
    if not kernels.kernel_events_complete(ctx):
        return None
    tr = ctx["trace"]
    return 1e6 * tr.op_seconds(kernels.is_ppot_alias_kernel) / ctx["host"]["turns"]
