"""Share of the one-mesh PCG iterations' roofline: the least time for the
traced iterations' work (`chipbench.work.pcg_iteration`, from the
problem) at the aggregate bandwidth and peak of the mesh's chips, over
the device busy time per device of the traced call, in percent."""
from chipbench.work import least_seconds


def read(ctx):
    tr, work = ctx["trace"], ctx["work"]
    its = ctx["counters"].get("traced_iterations")
    if not tr or tr["busy_s"] <= 0 or not its or not work:
        return None
    least, _ = least_seconds(work, ctx["peak"])
    return least / work["chips"] * its / tr["busy_s"] * 100.0
