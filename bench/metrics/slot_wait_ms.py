"""Mean time a miss waited in the decode scheduler for a wave, from
``BatchScheduler.submit`` to being given a slot: the change in the
scheduler's ``slot_wait_s`` over the change in its ``admitted``, in ms."""


def read(ctx):
    a, b = ctx.snap0.get("decode_slots"), ctx.snap1.get("decode_slots")
    if not a or not b or "slot_wait_s" not in b:
        return None
    n = b["admitted"] - a["admitted"]
    if n <= 0:
        return None
    return 1e3 * (b["slot_wait_s"] - a["slot_wait_s"]) / n
