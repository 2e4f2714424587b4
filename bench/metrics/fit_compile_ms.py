"""Time a training job spends tracing, lowering and compiling (or loading
from the persistent cache) its programs: the program's ``jit.trace_s`` +
``jit.lower_s`` + ``jit.compile_s`` counters over the job, averaged over the
window's jobs, in ms. Reads each job's ``counters`` (the registry's change
over that job)."""

PHASES = ("jit.trace_s", "jit.lower_s", "jit.compile_s")


def read(ctx):
    fits = [f for f in ctx.get("fits", [])
            if all(p in f.get("counters", {}) for p in PHASES)]
    if not fits:
        return None
    return 1e3 * sum(f["counters"][p] for f in fits for p in PHASES) / len(fits)
