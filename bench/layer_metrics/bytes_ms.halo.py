"""Device time under the ``tempi.bytes`` named scope per
predictor-corrector cycle, averaged over the cell's chips; nothing
where the trace carries no such scope."""


def read(ctx):
    seconds = ctx.get("scope_s", {}).get("tempi.bytes")
    if seconds is None:
        return None
    return seconds / ctx["work_units"] * 1e3
