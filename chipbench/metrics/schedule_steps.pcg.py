"""Steps of one application of the IC(0) preconditioner: the forward and
the backward sweep, each its T-factor preamble schedule plus its main
schedule (`chipbench.steps`), all of which run on the device inside the
PCG loop."""


def read(ctx):
    return ctx["counters"].get("pcg_steps")
