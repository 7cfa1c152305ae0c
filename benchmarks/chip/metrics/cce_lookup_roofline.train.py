"""The fused lookup's share of its roofline in training: the least time
for the lookup's own work (forward and backward of every step) over the
device time of the Pallas kernels (``tpu_custom_call``) in the window,
in %."""
from harness import weights, work


def read(ctx):
    red, peak, r = ctx["reduction"], ctx["peak"], ctx["run"]
    if ctx["mode"] != "train" or red is None or peak is None:
        return None
    kernel_s = red.ops_matching("tpu_custom_call")
    if not kernel_s:
        return None
    shapes = weights.table_shapes(ctx["cfg"])
    batch = r["examples"] // r["steps"]
    best = 0.0
    for backward in (False, True):
        adds, nbytes = work.lookup_work(shapes, batch, backward=backward,
                                        bag_sizes=ctx["cfg"].get("bag_sizes"))
        best += work.roofline_s(adds, nbytes, peak)
    return 100.0 * best * r["steps"] / kernel_s
