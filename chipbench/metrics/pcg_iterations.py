"""Mean CG iterations per solve of the window (`SolveResult.iterations`)."""


def read(ctx):
    its = ctx["counters"].get("iterations")
    return sum(its) / len(its) if its else None
