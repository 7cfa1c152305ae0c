"""Peak device memory of the run, ``peak_bytes_in_use`` after the window,
in MB (10**6 bytes)."""


def read(ctx):
    b = ctx["run"]["peak_bytes"]
    return None if b is None else b / 1e6
