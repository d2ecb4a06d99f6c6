"""The median latency of the window's hits, from the scheduled send to the
answer (host clock), counted as the end-to-end percentiles count them.
Read per layer: a hit either finds the device free or waits for the
decode chunk in flight, and the median of a window's 51 hits falls
between those two modes, so it spreads too widely from run to run to be
held to a bound (PERF.md)."""
from harness.readers import latency_pct_ms


def read(ctx):
    return latency_pct_ms(ctx, "hit", 50)
