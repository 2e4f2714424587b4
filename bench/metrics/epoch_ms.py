"""Wall time of one epoch on the engine's own spans: the window's
``engine.dispatch`` + ``engine.fetch`` seconds over the epochs run, in ms."""


def read(ctx):
    fits = [f for f in ctx.get("fits", []) if "spans_s" in f]
    epochs = sum(f["epochs"] for f in fits)
    if not epochs:
        return None
    return 1e3 * sum(f["spans_s"] for f in fits) / epochs
