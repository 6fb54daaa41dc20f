"""Device busy milliseconds per CG iteration, over the traced solves."""


def read(ctx):
    tr = ctx["trace"]
    its = ctx["counters"].get("traced_iterations")
    if not tr or tr["busy_s"] <= 0 or not its:
        return None
    return tr["busy_s"] / its * 1e3
