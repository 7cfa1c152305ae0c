"""Fold thread: the SpaceSaving head and ring bookkeeping of every tracked
feature (the ``fold-heads`` spans) per folded batch, in ms."""
from harness import spans


def read(ctx):
    return spans.ms_per_fold(ctx, "fold-heads")
