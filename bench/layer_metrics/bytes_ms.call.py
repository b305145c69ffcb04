"""Device time under the ``tempi.bytes`` named scope per pack + unpack
sequence; nothing where the trace carries no such scope."""


def read(ctx):
    seconds = ctx.get("scope_s", {}).get("tempi.bytes")
    if seconds is None:
        return None
    return seconds / ctx["calls"] * 1e3
