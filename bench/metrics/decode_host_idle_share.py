"""The chip waiting on the decode worker's own host work: the seconds in
which no operation ran on the device while a ``storinfer.decode.admit``,
``storinfer.decode.chunk`` or ``storinfer.decode.finish`` span was open
(tokenizing and prefilling a wave, dispatching a chunk and reading its
tokens back, retiring slots and answering), as a share of the traced
stretch of the window: from its start to the device's last operation.
The profiler's device buffer can fill before the window closes (about
3.5 million operations, some 39 s into a `novel` window on a v5e); the
untraced rest would otherwise read as idle."""
from harness import xtrace

DECODE_SPANS = ("storinfer.decode.admit", "storinfer.decode.chunk",
                "storinfer.decode.finish")


def _union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap_ns(a, b) -> int:
    """Total overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def traced_stretch(trace, device):
    """(window start, end of the device's last operation in the window)."""
    return trace.window[0], max(e for _, e in xtrace._op_intervals(device))


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices:
        return None
    shares = []
    for d in t.devices:
        w0, w1 = traced_stretch(t, d)
        spans = _union((max(s, w0), min(e, w1)) for name, s, e in t.host
                       if name in DECODE_SPANS and e > w0 and s < w1)
        if not spans or w1 <= w0:
            continue
        idle = _overlap_ns(xtrace._gaps(xtrace._op_intervals(d), (w0, w1)),
                           spans)
        shares.append(idle / (w1 - w0))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
