"""Share of the traced job during which the device ran no operation:
1 - (union of operation intervals) / (traced window), averaged over the
chips, in %."""


def read(ctx):
    red = ctx.get("trace")
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
