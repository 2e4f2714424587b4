"""Median time from a request's dispatch to its scores on the host (the
serving engine's pad, transfer, scoring and fetch), in ms."""
import numpy as np


def read(ctx):
    s = ctx.get("service_s")
    if s is None or not len(s):
        return None
    return float(np.quantile(s, 0.5, method="inverted_cdf") * 1e3)
