"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window, in %."""


def read(ctx):
    red = ctx["reduction"]
    if ctx["mode"] != "train" or red is None:
        return None
    return 100.0 * red.idle_share
