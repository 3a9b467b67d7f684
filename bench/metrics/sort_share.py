"""Share of device busy time spent in XLA sort instructions (the
completion flush's lexsort and the pending set's argsort): ops whose
instruction kind is ``sort``.  Sorts are nested in the scan's loop, so
the reading needs a trace that kept its nested events: absent unless the
dispatch kernel, nested beside them, has its event for every turn run."""
from bench import kernels
from bench.trace import op_kind


def _is_sort(name: str) -> bool:
    return op_kind(name) == "sort"


def read(ctx):
    tr = ctx["trace"]
    if tr.busy_s <= 0 or not kernels.kernel_events_complete(ctx):
        return None
    return 100.0 * tr.op_seconds(_is_sort) / tr.busy_s
