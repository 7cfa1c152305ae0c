"""Model FLOPs of the samples served in the window (forward only) over the
window's wall time and the chip's peak, in %."""
from harness import work


def read(ctx):
    r, peak = ctx["run"], ctx["peak"]
    if ctx["mode"] != "serve" or peak is None or not r["samples"]:
        return None
    flops = work.dlrm_forward_flops(ctx["cfg"]) * r["samples"]
    return 100.0 * flops / r["window_s"] / peak["flops_per_s"]
