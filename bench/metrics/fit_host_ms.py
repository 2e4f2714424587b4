"""Host time of a training job outside its epoch programs: the job's wall
time minus its ``engine.dispatch`` and ``engine.fetch`` spans (retrace,
compile-cache loads, start-up checks, placement, final loss), averaged over
the window's jobs, in ms."""


def read(ctx):
    fits = [f for f in ctx.get("fits", []) if "spans_s" in f]
    if not fits:
        return None
    return 1e3 * sum(f["dur_s"] - f["spans_s"] for f in fits) / len(fits)
