"""99th percentile of how late the load generator submitted a query after
its due time, in ms: a starved generator shows here, not as a fast server."""
import numpy as np


def read(ctx):
    x = ctx["run"].get("lateness_s") if ctx["mode"] == "serve" else None
    return None if x is None or not len(x) else 1e3 * float(np.percentile(x, 99))
