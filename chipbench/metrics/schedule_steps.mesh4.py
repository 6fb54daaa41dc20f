"""All_gather families of one application of the one-mesh IC(0)
preconditioner: the program's counter `sharded.exchanges` over the
lowering of both sweeps (each its T-factor preamble schedule and its
main schedule), one family per schedule step.  Nothing is read from a
program without the counter."""


def read(ctx):
    return ctx["counters"].get("exchanges") or None
