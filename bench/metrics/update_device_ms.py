"""Device time of an epoch's step size and update (the logits and the
factored iterate): the device operations under the ``dfw.linesearch`` and
``dfw.update`` scopes in the traced job, per epoch of that job, in ms. The
two scopes follow one another and never nest, so the sum of their unions
is the union of both. Reads the trace's ``scope_s`` (``bench/scopes.py``)."""

SCOPES = ("dfw.linesearch", "dfw.update")


def read(ctx):
    fit = next((f for f in ctx.get("fits", []) if f.get("traced")), None)
    red = ctx.get("trace")
    if fit is None or red is None or not fit["epochs"]:
        return None
    by_scope = red.get("scope_s", {})
    if not any(s in by_scope for s in SCOPES):
        return None
    return 1e3 * sum(by_scope.get(s, 0.0) for s in SCOPES) / fit["epochs"]
