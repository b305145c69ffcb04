"""Device time of collective operations per predictor-corrector cycle,
averaged over the cell's chips; nothing where the trace holds none."""


def read(ctx):
    seconds = ctx["class_s"].get("collective")
    if not seconds:
        return None
    return seconds / ctx["work_units"] * 1e3
