"""Time per step the training loop waits to put a batch into the sketch
tracker's full fold queue (the ``sketch-enqueue-wait`` spans), in ms."""
from harness import spans


def read(ctx):
    return spans.ms_per_step(ctx, "sketch-enqueue-wait")
