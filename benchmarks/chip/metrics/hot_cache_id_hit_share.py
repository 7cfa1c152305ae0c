"""Share of the window's id lookups answered by the hot cache, from the
engine's counters (``n_id_hits / n_id_lookups``), in %."""


def read(ctx):
    if ctx["mode"] != "serve":
        return None
    c = ctx["run"]["counters"]
    return 100.0 * c["n_id_hits"] / c["n_id_lookups"] if c.get("n_id_lookups") else None
