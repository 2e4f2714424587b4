"""Epochs a job ran to reach its gap (``DFWFitResult.epochs_run``), averaged
over the window's jobs: a count, taken from the program."""


def read(ctx):
    fits = ctx.get("fits", [])
    if not fits:
        return None
    return sum(f["epochs"] for f in fits) / len(fits)
