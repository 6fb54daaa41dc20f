"""Percent of the traced window in which the mesh's devices ran no
operation, averaged over the devices."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["busy_s"] <= 0:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
