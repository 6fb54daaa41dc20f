"""Mean refinement rounds per solve, from the program's `operator.refine`
spans of the window."""


def read(ctx):
    rounds = [attrs["rounds"] for name, attrs in ctx["spans"]
              if name == "operator.refine" and "rounds" in attrs]
    return sum(rounds) / len(rounds) if rounds else None
