"""99th percentile of how late requests were dispatched: from due time to
``score_async``, in ms. It reads the queue in front of the engine and a
load generator that falls behind."""
import numpy as np


def read(ctx):
    q = ctx.get("queue_s")
    if q is None or not len(q):
        return None
    return float(np.quantile(q, 0.99, method="inverted_cdf") * 1e3)
