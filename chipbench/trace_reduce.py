"""Reduce a profiler trace to device busy and idle time.

`load` reads the `.xplane.pb` that `jax.profiler` wrote into a plain
record, `reduce` turns a record into numbers:

* the window: the interval of the host span named `window` (the harness
  wraps the traced calls in `chipbench.window`);
* busy time: the union of the intervals of the device's operations
  ("XLA Ops" line of each `/device:` plane) inside the window, averaged
  over the devices that ran anything; idle share is 1 - busy / window;
* device time by operation: each operation's self time (its duration less
  that of the operations nested inside it, such as a loop's body), summed
  by the operation's name over the window and the devices;
* idle gaps: the intervals of the window in which the first device ran
  nothing, each put down to the innermost host span around its midpoint,
  summed by that span's name: the window's own name where the host was
  between calls.

A record is a dict: `{"devices": {plane: [[name, start_ns, dur_ns], ...]},
"host": [[name, start_ns, dur_ns], ...]}`; `load` keeps only the host
spans whose names it is given, so the program's own events do not crowd
the labels.  Times are nanoseconds on the profiler's clock, which it
shares between host and device events.
"""
from __future__ import annotations

import glob
import os

import numpy as np

__all__ = ["load", "reduce", "NO_SPAN"]

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"
NO_SPAN = "no host span"


def _short(name: str) -> str:
    """'%fusion.8 = f32[256]{0} fusion(...)' -> 'fusion.8'."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str, host_names) -> dict:
    """Read the newest `.xplane.pb` under `trace_dir` (module doc)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    host_names = set(host_names)
    record = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            short: dict = {}
            events = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name
                    s = short.get(name)
                    if s is None:
                        s = short[name] = _short(name)
                    events.append([s, ev.start_ns, ev.duration_ns])
            if events:
                record["devices"][plane.name] = events
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host_names:
                        record["host"].append(
                            [ev.name, ev.start_ns, ev.duration_ns])
    return record


def _union(starts: np.ndarray, ends: np.ndarray) -> tuple:
    """Disjoint, sorted intervals covering [starts[i], ends[i])."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, dtype=bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    seg_end = np.append(idx[1:] - 1, s.size - 1)
    return s[idx], reach[seg_end]


def _self_times(events, lo: float, hi: float) -> dict:
    """Self time by name of events clipped to [lo, hi): nested events are
    taken out of their parent's time."""
    evs = sorted(((max(st, lo), min(st + d, hi), name)
                  for name, st, d in events
                  if st < hi and st + d > lo), key=lambda t: (t[0], -t[1]))
    out: dict = {}
    stack: list = []            # [end, name, self]
    for st, en, name in evs:
        while stack and stack[-1][0] <= st:
            _, nm, self_ = stack.pop()
            out[nm] = out.get(nm, 0.0) + self_
        if stack:
            stack[-1][2] -= en - st
        stack.append([en, name, en - st])
    for _, nm, self_ in stack:
        out[nm] = out.get(nm, 0.0) + self_
    return out


def _label_gaps(gs: np.ndarray, ge: np.ndarray, host) -> dict:
    """Sum of gap length by the innermost host span around each gap's
    midpoint: the enclosing span that started last, the shorter of two
    that started together."""
    out: dict = {}
    if gs.size == 0:
        return out
    names = [h[0] for h in host]
    hs = np.asarray([h[1] for h in host], dtype=np.float64)
    he = hs + np.asarray([h[2] for h in host], dtype=np.float64)
    mid = (gs + ge) / 2
    for i in range(gs.size):
        inside = np.flatnonzero((hs <= mid[i]) & (he >= mid[i]))
        if inside.size:
            last = inside[np.lexsort((he[inside], -hs[inside]))[0]]
            label = names[last]
        else:
            label = NO_SPAN
        out[label] = out.get(label, 0.0) + float(ge[i] - gs[i])
    return out


def reduce(record: dict, window: str = "chipbench.window",
           count: str = "chipbench.solve") -> dict:
    """Numbers of one traced window (module doc), in seconds.

    Returns {"window_s", "busy_s", "idle_share", "devices", "calls",
    "ops": [[name, s], ...], "gaps": [[span, s], ...]} with ops and gaps
    sorted longest first; `calls` counts the `count` host spans that lie
    inside the window.  Raises ValueError when the window span is
    missing."""
    spans = [h for h in record["host"] if h[0] == window]
    if not spans:
        raise ValueError(f"no {window!r} host span in the trace")
    lo = float(min(h[1] for h in spans))
    hi = float(max(h[1] + h[2] for h in spans))
    busy, ops = [], {}
    first_gaps = None
    for plane in sorted(record["devices"]):
        evs = record["devices"][plane]
        st = np.asarray([e[1] for e in evs], dtype=np.float64)
        en = st + np.asarray([e[2] for e in evs], dtype=np.float64)
        keep = (st < hi) & (en > lo)
        if not keep.any():
            continue
        us, ue = _union(np.clip(st[keep], lo, hi), np.clip(en[keep], lo, hi))
        busy.append(float((ue - us).sum()))
        for name, t in _self_times(evs, lo, hi).items():
            ops[name] = ops.get(name, 0.0) + t
        if first_gaps is None:
            edges_s = np.concatenate([[lo], ue])
            edges_e = np.concatenate([us, [hi]])
            open_ = edges_e > edges_s
            first_gaps = (edges_s[open_], edges_e[open_])
    gaps = _label_gaps(*first_gaps, record["host"]) if first_gaps else \
        {window: hi - lo}
    window_ns = hi - lo
    busy_ns = float(np.mean(busy)) if busy else 0.0
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns if window_ns > 0 else None,
        "devices": len(busy),
        "calls": sum(1 for h in record["host"]
                     if h[0] == count and h[1] >= lo and h[1] + h[2] <= hi),
        "ops": sorted(([k, v * 1e-9] for k, v in ops.items()),
                      key=lambda kv: -kv[1]),
        "gaps": sorted(([k, v * 1e-9] for k, v in gaps.items()),
                       key=lambda kv: -kv[1]),
    }
