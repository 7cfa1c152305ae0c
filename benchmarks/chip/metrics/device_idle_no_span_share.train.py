"""Share of the window in which the device is idle while no span of the
training loop's thread is open (the harness's ``SPAN_NAMES`` and the loop's
newer spans; never the fold thread's, which overlap them), in %."""
from harness import spans
from harness import trace as tr


def read(ctx):
    trace = ctx["trace"]
    if ctx["mode"] != "train" or trace is None or not spans.in_window(trace, "next-batch"):
        return None
    red = tr.reduce(trace, tr.SPAN_NAMES + spans.LOOP_SPANS)
    if red is None:
        return None
    return 100.0 * red.idle_gaps.get(tr.NO_SPAN, 0.0) / red.window_s
