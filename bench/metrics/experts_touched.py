"""Share of the held-expert weights a decode step reads that some live
slot routed to, in the expert layers: 100 x the change in the scheduler's
``experts_touched`` (held experts that at least one live slot routed to,
over expert layers and steps) over the change in its ``experts_read``
(held experts those layer steps read). It rises with more live slots a
step, and to 100% with a layer that reads only the touched experts: it
measures what reading only those could save, not what the layer costs.
Nothing to read for a model without expert layers, or a program without
the counters."""


def read(ctx):
    a, b = ctx.snap0.get("decode_slots"), ctx.snap1.get("decode_slots")
    if not a or not b or "experts_read" not in b:
        return None
    n = b["experts_read"] - a["experts_read"]
    if n <= 0:
        return None
    return 100.0 * (b["experts_touched"] - a["experts_touched"]) / n
