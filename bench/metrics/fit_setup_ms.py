"""Set-up of a training job inside the program: its ``engine.setup`` span,
from entering ``launch/dfw.fit`` to the first segment dispatch (kernel
checks, placement of data and state), averaged over the window's jobs, in
ms. Reads each job's ``span_s`` (seconds by span name, that job only)."""


def read(ctx):
    fits = [f for f in ctx.get("fits", [])
            if "engine.setup" in f.get("span_s", {})]
    if not fits:
        return None
    return 1e3 * sum(f["span_s"]["engine.setup"] for f in fits) / len(fits)
