"""The decode step's share of its roofline: the least time of a step on
this chip (its bytes over HBM bandwidth, or its FLOPs over the peak, as
the configuration's architecture module counts them for its live slots)
over the measured device time per step. Live slots and their KV
positions per step are the window's served tokens and their positions
over the traced decode steps."""
from harness import costs
from harness.readers import decode_steps, token_positions


def read(ctx):
    got = decode_steps(ctx)
    if got is None or ctx.peaks is None:
        return None
    secs, steps = got
    pos = list(token_positions(ctx))
    if not pos:
        return None
    live = len(pos) / steps
    kv = sum(pos) / steps
    least, _bound = costs.decode_step_least_s(ctx.arch, ctx.cfg, ctx.peaks,
                                              live, kv)
    return 100.0 * least / (secs / steps)
