"""Examples the train step completed in the window over the window's wall
time; the clock stops once the final state is ready."""


def read(ctx):
    if ctx["mode"] != "train":
        return None
    r = ctx["run"]
    return r["examples"] / r["window_s"]
