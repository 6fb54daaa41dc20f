"""Share of the sweep's roofline: the least time for one call's work
(`chipbench.work.sweep`, from the problem) over the device busy time per
traced call, in percent."""
from chipbench.work import least_seconds


def read(ctx):
    tr, work = ctx["trace"], ctx["work"]
    if not tr or tr["busy_s"] <= 0 or not tr["calls"] or not work:
        return None
    least, _ = least_seconds(work, ctx["peak"])
    return least / (tr["busy_s"] / tr["calls"]) * 100.0
