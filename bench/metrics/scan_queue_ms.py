"""How long a search sat behind the device's queue. For each
``storinfer.search.scan`` span (the program's flat scan, from its call to
its results on the host) that starts in the window: the start of the
first execution of the int8 scan program at or after the span's start
that no earlier span took, less the span's start. The mean, in ms. A scan
that finds the device free waits for its own dispatch; one that lands on
a decode chunk waits for the rest of it.

Scans run one at a time and each ends inside its span, so the span's
execution is the first not taken that ends after the span's start. That
reading of "at or after" keeps a span whose scan the device began a
fraction of a millisecond before the host's clock says the span did, and
a span whose execution is missing from the trace (none starts before the
span ends) is left out rather than given the next span's scan."""
from harness.readers import SCAN_PROGRAM

SCAN_SPAN = "storinfer.search.scan"


def matches(trace):
    """(span start, span end, program start, program end), in ns, for
    each scan span that starts in the window and the scan execution it
    took, in order."""
    spans = sorted((s, e) for name, s, e in trace.host
                   if name == SCAN_SPAN and s >= trace.window[0])
    runs = sorted((s, e) for d in trace.devices for name, s, e in d.modules
                  if SCAN_PROGRAM in name)
    out, j = [], 0
    for s, e in spans:
        while j < len(runs) and runs[j][1] < s:
            j += 1
        if j == len(runs):
            break
        if runs[j][0] <= e:
            out.append((s, e) + runs[j])
            j += 1
    return out


def read(ctx):
    if ctx.trace is None:
        return None
    got = matches(ctx.trace)
    if not got:
        return None
    return 1e-6 * sum(p0 - s0 for s0, _, p0, _ in got) / len(got)
