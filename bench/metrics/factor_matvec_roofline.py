"""Share of its roofline that the scoring kernel reaches: the required work
of the traced requests' rows at the model's live rank (``bench/work.py``;
padding rows not counted) at the roofline, over the device time of the
``factor_matvec`` kernel events, in %."""
from bench import peaks, trace_reduce, work

# Names of the kernel's events in the device trace.
KERNEL = ("factor_matvec",)


def read(ctx):
    red = ctx.get("trace")
    if red is None or not ctx.get("traced_requests"):
        return None
    kernel_s = trace_reduce.op_seconds(red, KERNEL)
    if kernel_s <= 0:
        return None
    need = sum(peaks.roofline_s(*work.factor_matvec_batch(
        int(ctx["sizes"][i]), ctx["d"], ctx["m"], ctx["rank"]),
        ctx["device_kind"])[0] for i in ctx["traced_requests"])
    return 100.0 * need / kernel_s
