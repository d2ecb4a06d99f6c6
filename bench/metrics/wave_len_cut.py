"""Share of decode waves closed because the next waiting miss's prompt
length differed while a slot was still free: the change in the
scheduler's ``len_cuts`` over the change in its ``waves``, in %."""


def read(ctx):
    a, b = ctx.snap0.get("decode_slots"), ctx.snap1.get("decode_slots")
    if not a or not b or "len_cuts" not in b or b["waves"] <= a["waves"]:
        return None
    return 100.0 * (b["len_cuts"] - a["len_cuts"]) / (b["waves"] - a["waves"])
