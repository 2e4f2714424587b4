"""Device time of an epoch's power iterations (the ``power_matvec`` kernels,
the softmax recompute and the exchanges among them): the union of the
device operations under the ``dfw.power`` scope in the traced job, per epoch
of that job, in ms. Reads the trace's ``scope_s`` (``bench/scopes.py``)."""


def read(ctx):
    fit = next((f for f in ctx.get("fits", []) if f.get("traced")), None)
    red = ctx.get("trace")
    if fit is None or red is None or not fit["epochs"]:
        return None
    s = red.get("scope_s", {}).get("dfw.power")
    if s is None:
        return None
    return 1e3 * s / fit["epochs"]
