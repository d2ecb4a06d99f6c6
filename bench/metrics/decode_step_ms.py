"""Device time of one decode step: the decode-chunk programs' device
time over (executions x chunk), from the trace."""
from harness.readers import decode_steps


def read(ctx):
    got = decode_steps(ctx)
    return None if got is None else 1e3 * got[0] / got[1]
