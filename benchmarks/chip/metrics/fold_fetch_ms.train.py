"""Fold thread: the device-to-host copy of a batch's count-min cell delta
(the ``fold-fetch`` spans) per folded batch, in ms."""
from harness import spans


def read(ctx):
    return spans.ms_per_fold(ctx, "fold-fetch")
