"""Fold thread: the count-min cell adds of every tracked feature (the
``fold-cells`` spans) per folded batch, in ms."""
from harness import spans


def read(ctx):
    return spans.ms_per_fold(ctx, "fold-cells")
