"""Mean time a hit waited between its search returning and the resolve
stage reading its pair from the store (``PipelineStats`` "resolve"
stage), over the window."""
from harness.readers import stage_wait_ms


def read(ctx):
    return stage_wait_ms(ctx, "resolve")
