"""The 95th percentile latency of the window's hits, from the scheduled
send to the answer (host clock), counted as the end-to-end percentiles
count them. Read per layer: a window holds about 51 hits, so only two or
three lie beyond it, and it spreads too widely from run to run to be held
to a bound; ``hit_p80_ms`` is the hit tail held end to end (PERF.md)."""
from harness.readers import latency_pct_ms


def read(ctx):
    return latency_pct_ms(ctx, "hit", 95)
