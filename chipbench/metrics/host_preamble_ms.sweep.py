"""Host milliseconds per traced call in the program's `engine.preamble`
spans: the T-factor preamble, run in Python on the host."""
from chipbench.host_spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "engine.preamble")
