"""Time per step the training loop spends closing sketch windows (the
``sketch-window-close`` spans: the flush barrier, the summary, the decay),
in ms."""
from harness import spans


def read(ctx):
    return spans.ms_per_step(ctx, "sketch-window-close")
