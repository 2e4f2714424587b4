"""Executables a training job builds or loads from the persistent cache:
the program's ``jit.compiles`` counter over the job, averaged over the
window's jobs. A count, taken from the program; 0 when every program a job
runs is already compiled in the process."""


def read(ctx):
    fits = [f for f in ctx.get("fits", [])
            if "jit.compiles" in f.get("counters", {})]
    if not fits:
        return None
    return sum(f["counters"]["jit.compiles"] for f in fits) / len(fits)
