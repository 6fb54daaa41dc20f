"""Host span time in a traced window.

`chipbench.trace_reduce` puts each idle gap of the device down to the
innermost host span around it.  This module sums the time of each host
span inside the window, by name (`host_s`), so that a metric can read how
long the host spent in one part of a call (the program's spans share the
profiler's clock when its tracer annotates JAX, as the harness's traced
run has it).

`read(ctx)` does this once per traced run, for the metric readers: it
finds the run's trace by its directory (the newest `chipbench-trace-*`
under the temporary directory, as the harness makes it), checks that its
window is the one `ctx["trace"]` reduced, keeps the result in `ctx` and
prints it on a `chipbench: host_spans` line.  It returns None where there
is no such trace, or where no device ran in the window (a run on the
CPU).
"""
from __future__ import annotations

import glob
import json
import os
import tempfile

from chipbench import trace_reduce

__all__ = ["reduce", "read", "per_call_ms"]

TRACE_PREFIX = "chipbench-trace-"
WINDOW = "chipbench.window"


def reduce(record: dict, window: str = WINDOW) -> dict:
    """{"window_s": s, "host_s": {span: s}} of a `trace_reduce` record:
    host spans count only their part inside the window, which itself is
    left out.  Raises ValueError when the window span is missing."""
    spans = [h for h in record["host"] if h[0] == window]
    if not spans:
        raise ValueError(f"no {window!r} host span in the trace")
    lo = min(h[1] for h in spans)
    hi = max(h[1] + h[2] for h in spans)
    host: dict = {}
    for name, st, d in record["host"]:
        inside = min(st + d, hi) - max(st, lo)
        if name != window and inside > 0:
            host[name] = host.get(name, 0.0) + inside * 1e-9
    return {"window_s": (hi - lo) * 1e-9, "host_s": host}


def _trace_dir():
    dirs = glob.glob(os.path.join(tempfile.gettempdir(),
                                  TRACE_PREFIX + "*"))
    return max(dirs, key=os.path.getmtime) if dirs else None


def read(ctx: dict):
    """Host span time of the traced run whose reduction is `ctx["trace"]`
    (module doc), or None."""
    if "host_spans" in ctx:
        return ctx["host_spans"]
    ctx["host_spans"] = None
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None             # no device ran: not a chip trace
    trace_dir = _trace_dir()
    if trace_dir is None:
        return None
    names = {WINDOW, "chipbench.solve"} | {
        name for name, _ in ctx.get("spans", ())}
    try:
        red = reduce(trace_reduce.load(trace_dir, names))
    except (FileNotFoundError, ValueError):
        return None
    if abs(red["window_s"] - tr["window_s"]) > 1e-9 * tr["window_s"]:
        return None             # another run's trace
    ctx["host_spans"] = red
    print("chipbench: host_spans " + json.dumps(red), flush=True)
    return red


def per_call_ms(ctx: dict, span: str):
    """Milliseconds of the host span `span` per traced call, or None."""
    red = read(ctx)
    calls = ctx["trace"]["calls"] if ctx.get("trace") else 0
    if red is None or not calls or span not in red["host_s"]:
        return None
    return red["host_s"][span] / calls * 1e3
