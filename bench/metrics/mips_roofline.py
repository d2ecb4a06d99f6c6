"""The int8 MIPS kernel's share of its roofline: the least time of its
calls on this chip (the store's int8 rows and f32 scales read once per
call, and the queries, over HBM bandwidth, or its int8 operations over the
int8 peak, whichever is larger) over the kernel's device time in the
trace. Queries per call are the window's searched requests over its
search batches."""
from harness import costs
from harness.readers import SCAN_PROGRAM, search_batches, searched


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    times = ctx.trace.kernel_times(SCAN_PROGRAM)
    n_batches = search_batches(ctx)
    if not times or n_batches <= 0:
        return None
    q = searched(ctx) / n_batches
    least, _bound = costs.scan_least_s(ctx.store_rows,
                                       ctx.cfg["store"]["dim"], q, ctx.peaks)
    return 100.0 * least * len(times) / sum(times)
