"""Host time per step of the Trainer's ``sketch-fold`` span (no span nests
inside it, so this is its self time), from the trace, in ms."""


def read(ctx):
    tr, r = ctx["trace"], ctx["run"]
    if ctx["mode"] != "train" or tr is None:
        return None
    from harness.trace import window

    w = window(tr)
    spans = [e for e in tr.host if e.name == "sketch-fold"
             and w is not None and w[0] <= e.start_ns < w[1]]
    if not spans:
        return None
    return 1e-6 * sum(e.dur_ns for e in spans) / r["steps"]
