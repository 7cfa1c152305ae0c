"""The fused lookup's share of its roofline in serving: the least time for
the cold launches' own work (the table read once per launch, and each
missed id's rows and output) over the device time of the Pallas kernels
(``tpu_custom_call``) in the window, in %."""
from harness import weights, work


def read(ctx):
    red, peak, r = ctx["reduction"], ctx["peak"], ctx["run"]
    if ctx["mode"] != "serve" or red is None or peak is None:
        return None
    kernel_s = red.ops_matching("tpu_custom_call")
    c = r["counters"]
    if not kernel_s or not c.get("n_launches"):
        return None
    shapes = weights.table_shapes(ctx["cfg"])
    misses = c["n_id_lookups"] - c["n_id_hits"]
    adds, nbytes = work.lookup_misses_work(shapes, misses, c["n_launches"])
    return 100.0 * work.roofline_s(adds, nbytes, peak) / kernel_s
