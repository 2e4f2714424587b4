"""Share of the chip's peak that a whole epoch reaches: the epoch's required
work (``bench/work.py``, one chip's rows) at the roofline of
``bench/peaks.py``, over the epoch's wall time on the engine's spans
(``engine.dispatch`` + ``engine.fetch`` per epoch), in %."""
from bench import peaks


def read(ctx):
    fits = [f for f in ctx.get("fits", []) if "spans_s" in f]
    epochs = sum(f["epochs"] for f in fits)
    if not epochs:
        return None
    need, _ = peaks.roofline_s(*ctx["epoch_work"], ctx["device_kind"])
    return 100.0 * need * epochs / sum(f["spans_s"] for f in fits)
