"""Share of its roofline that the fused alias PPoT-SQ(2) dispatch kernel
reaches: the least time the chip needs for one call (``bench/kernels.py``:
the bytes it must move over the HBM bandwidth, or its operations over the
peak, whichever is larger) times the turns run, over the kernel's device
time.  Absent where the trace holds no kernel event, or holds it for fewer
turns than ran (events were dropped)."""
from bench import kernels


def read(ctx):
    if not kernels.kernel_events_complete(ctx):
        return None
    tr = ctx["trace"]
    cfg = ctx["cell"].config
    ops, nbytes = kernels.ppot_alias_cost(cfg["arrival_batch"], cfg["n"])
    t_min = kernels.least_time_s(ops, nbytes, ctx["device_kind"])
    turns = ctx["host"]["turns"]
    return 100.0 * turns * t_min / tr.op_seconds(kernels.is_ppot_alias_kernel)
