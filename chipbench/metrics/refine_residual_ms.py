"""Host milliseconds per traced refined call in the program's
`operator.residual` spans: each float64 residual b - L x of the
refinement loop and its max."""
from chipbench.host_spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "operator.residual")
