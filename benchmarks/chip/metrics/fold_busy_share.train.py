"""Share of the window in which the sketch tracker's fold thread is folding
a batch: the union of its ``fold-batch`` spans, clipped to the window, in %.
Near 100 the fold thread sets the pace of training."""
from harness import spans


def read(ctx):
    return spans.busy_share(ctx, "fold-batch")
