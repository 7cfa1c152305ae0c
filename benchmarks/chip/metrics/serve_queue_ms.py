"""Mean time from a sample's due time to the dispatch of its micro-batch,
in ms: the micro-batcher's wait plus any backlog."""


def read(ctx):
    q = ctx["run"].get("queue_s") if ctx["mode"] == "serve" else None
    return None if q is None or not len(q) else 1e3 * float(q.mean())
