"""Share of the chip's peak that scoring reaches over the whole window: the
required work of every request's rows (``bench/work.py``; padding rows not
counted) at the roofline, over the window's wall time, in %."""
from bench import peaks, work


def read(ctx):
    sizes = ctx.get("sizes")
    if sizes is None or not len(sizes) or ctx["window_s"] <= 0:
        return None
    need = sum(peaks.roofline_s(*work.factor_matvec_batch(
        int(b), ctx["d"], ctx["m"], ctx["rank"]), ctx["device_kind"])[0]
        for b in sizes)
    return 100.0 * need / ctx["window_s"]
