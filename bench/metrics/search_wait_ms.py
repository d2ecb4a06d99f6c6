"""Mean time a request waited between ``submit`` and the search stage
picking it up (``PipelineStats`` "search" stage), over the window."""
from harness.readers import stage_wait_ms


def read(ctx):
    return stage_wait_ms(ctx, "search")
