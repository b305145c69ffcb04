"""Device time under the ``tempi.pack`` named scope per pack + unpack
sequence; nothing where the trace carries no such scope."""


def read(ctx):
    seconds = ctx.get("scope_s", {}).get("tempi.pack")
    if seconds is None:
        return None
    return seconds / ctx["calls"] * 1e3
