"""Share of its roofline that the power-iteration kernel reaches in the
traced job: the power iterations' required kernel work (``bench/work.py``:
passes over X, and over R for least squares, with a probe as wide as the
block) at the roofline, over the device time of the ``power_matvec`` kernel
events on one chip, in %."""
from bench import peaks, trace_reduce

# Names of the kernel's events in the device trace.
KERNEL = ("matvec",)


def read(ctx):
    fit = next((f for f in ctx.get("fits", []) if f.get("traced")), None)
    red = ctx.get("trace")
    if fit is None or red is None:
        return None
    kernel_s = trace_reduce.op_seconds(red, KERNEL)
    if kernel_s <= 0:
        return None
    need, _ = peaks.roofline_s(*ctx["power_work"], ctx["device_kind"])
    return 100.0 * need * fit["epochs"] / kernel_s
