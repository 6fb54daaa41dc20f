"""Device busy milliseconds per refined `solve` call (its sweeps and
casts), over the traced calls."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["busy_s"] <= 0 or not tr["calls"]:
        return None
    return tr["busy_s"] / tr["calls"] * 1e3
