"""Share of the device's busy time in operations under no ``tempi.``
named scope (datatype call cells); nothing where the trace carries no
scope at all."""


def read(ctx):
    if not ctx.get("scope_s") or ctx["busy_s"] <= 0:
        return None
    return 100.0 * ctx["unscoped_s"] / ctx["busy_s"]
