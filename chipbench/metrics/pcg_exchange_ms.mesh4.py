"""Device self time in collective operations per traced CG iteration,
averaged over the mesh's devices: the sweeps' per-step all_gathers and
the SpMV's psum, synchronous or as async start and done ops.  On the
v5e the trace names them `all-reduce.<k>` (the per-step all_gathers too)
and `psum.<k>`.  Nothing is read where the trace holds no such
operation."""

COLLECTIVES = ("all-gather", "all-reduce", "psum")


def read(ctx):
    tr = ctx["trace"]
    its = ctx["counters"].get("traced_iterations")
    if not tr or tr["busy_s"] <= 0 or not its or not tr["devices"]:
        return None
    found = [s for name, s in tr["ops"] if name.startswith(COLLECTIVES)]
    if not found:
        return None
    return sum(found) / tr["devices"] / its * 1e3
