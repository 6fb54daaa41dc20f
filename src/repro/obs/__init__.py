"""repro.obs — unified observability layer (docs/observability.md).

Four pieces, all zero-dependency and off-by-default:

* `trace`   — structured nested spans over the solve path; no-op unless
  enabled (`obs.enable()` / `REPRO_TRACE=1`).
* `metrics` — the counters/gauges/histograms registry every stats plane
  (`OperatorStats`, `ServiceStats`, registry lifecycle counters,
  portfolio tune counters) is a view over.
* `profile` — the per-step schedule profiler + `ProfilingEngine` wrapper
  (collective vs. compute split on the sharded path); feeds
  `CostModel.calibrate`.
* `export`  — Chrome trace-event and Prometheus text exporters plus
  the validators CI runs.

Quick trace of a solve::

    from repro import obs
    obs.enable()
    op.solve(b)
    obs.export.write_chrome_trace("solve.trace.json", obs.get_tracer())

`profile` is loaded lazily: it needs `repro.solver`, which itself
traces through this package — eager import here would be a cycle.
"""
from __future__ import annotations

import importlib

from . import export, metrics, trace
from .metrics import MetricsRegistry, default_registry
from .trace import (NULL_SPAN, Span, Tracer, disable, enable, enabled,
                    event, get_tracer, record_span, span)

__all__ = ["trace", "metrics", "export", "profile",
           "Span", "Tracer", "enable", "disable", "enabled", "get_tracer",
           "span", "event", "record_span", "NULL_SPAN",
           "MetricsRegistry", "default_registry"]


def __getattr__(name):
    if name == "profile":
        mod = importlib.import_module(".profile", __name__)
        globals()["profile"] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
