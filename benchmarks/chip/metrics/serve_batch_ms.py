"""Host clock around each ``engine.step()`` that served a micro-batch
(slots, translation, device, transfer back), mean, in ms."""


def read(ctx):
    b = ctx["run"].get("batch_s") if ctx["mode"] == "serve" else None
    return None if b is None or not len(b) else 1e3 * float(b.mean())
