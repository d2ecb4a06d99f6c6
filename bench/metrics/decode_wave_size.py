"""Requests admitted per decode wave over the window: the change in the
scheduler's ``admitted`` over the change in its ``waves``."""


def read(ctx):
    a, b = ctx.snap0.get("decode_slots"), ctx.snap1.get("decode_slots")
    if not a or not b or b["waves"] <= a["waves"]:
        return None
    return (b["admitted"] - a["admitted"]) / (b["waves"] - a["waves"])
