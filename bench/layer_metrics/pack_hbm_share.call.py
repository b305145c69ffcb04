"""Share of HBM peak bandwidth that a pack + unpack sequence reaches:
the bytes its description needs to move, at the chip's peak, over the
device busy time of one sequence."""


def read(ctx):
    if ctx["busy_s"] <= 0:
        return None
    least = ctx["needed_bytes_per_call"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ctx["busy_s"] / ctx["calls"])
