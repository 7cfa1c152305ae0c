"""Model FLOPs of the examples trained in the window (MLPs and the
upper-triangle interaction, forward and backward) over the window's wall
time and the chip's peak, in %."""
from harness import work


def read(ctx):
    r, peak = ctx["run"], ctx["peak"]
    if ctx["mode"] != "train" or peak is None:
        return None
    flops = work.dlrm_train_flops(ctx["cfg"]) * r["examples"]
    return 100.0 * flops / r["window_s"] / peak["flops_per_s"]
