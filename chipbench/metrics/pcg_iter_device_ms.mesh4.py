"""Device busy milliseconds per traced CG iteration of the one-mesh PCG,
averaged over the mesh's devices.  The traced call runs the capped
executable (`trace_iterations` iterations), whose first M^-1
application, before the loop, is counted with them."""


def read(ctx):
    tr = ctx["trace"]
    its = ctx["counters"].get("traced_iterations")
    if not tr or tr["busy_s"] <= 0 or not its:
        return None
    return tr["busy_s"] / its * 1e3
