"""The window's whole work as a share of the chip's peak: the model FLOPs
of every token the window's finished misses served (matmuls and attention
at each token's position) at the peak of the configuration's stated
dtype, plus the operations of every search (2 x rows x dim per query) at
the int8 peak, as time at peak over the window."""
from harness.readers import model_flops, searched


def read(ctx):
    if ctx.peaks is None:
        return None
    peak = ctx.peaks["flops_per_s"]
    scan = 2.0 * ctx.store_rows * ctx.cfg["store"]["dim"] * searched(ctx)
    at_peak = (model_flops(ctx) / peak[ctx.cfg["torch_dtype"]]
               + scan / peak["int8"])
    if at_peak <= 0:
        return None
    return 100.0 * at_peak / ctx.window_s
