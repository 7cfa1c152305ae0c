"""Device time of the train step program per step, from the trace's
``XLA Modules`` line, in ms."""


def read(ctx):
    red = ctx["reduction"]
    if ctx["mode"] != "train" or red is None:
        return None
    names = [n for n in red.module_s if "train_step" in n]
    n = sum(red.module_n[k] for k in names)
    return 1e3 * sum(red.module_s[k] for k in names) / n if n else None
