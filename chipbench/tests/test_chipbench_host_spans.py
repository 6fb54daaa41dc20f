"""Host span time of a traced window, and the readers of the metrics made
from it."""
import pytest

from chipbench import harness, host_spans, load_module

MS = 1_000_000            # nanoseconds


def _record():
    """A window of 100 ms: host spans inside it, partly outside it and
    after it, as `trace_reduce.load` returns them."""
    return {"devices": {}, "host": [
        ["chipbench.window", 10 * MS, 100 * MS],
        ["engine.preamble", 5 * MS, 10 * MS],      # half inside
        ["engine.preamble", 50 * MS, 6 * MS],
        ["operator.residual", 105 * MS, 10 * MS],  # half inside
        ["engine.put", 120 * MS, 1 * MS],          # after the window
    ]}


def test_reduce_counts_host_spans_only_inside_the_window():
    red = host_spans.reduce(_record())
    assert red["window_s"] == pytest.approx(0.1)
    assert red["host_s"] == pytest.approx({"engine.preamble": 0.011,
                                           "operator.residual": 0.005})


def test_reduce_without_a_window_raises():
    rec = _record()
    rec["host"] = rec["host"][1:]
    with pytest.raises(ValueError, match="chipbench.window"):
        host_spans.reduce(rec)


def _ctx(busy_s=0.02, window_s=0.1, calls=2, spans=()):
    return {"trace": {"busy_s": busy_s, "window_s": window_s,
                      "calls": calls},
            "counters": {}, "spans": list(spans), "peak": None,
            "work": None}


@pytest.fixture
def found_trace(monkeypatch, tmp_path):
    """`read` finds a trace directory and loads `_record()` from it; the
    loads are counted."""
    loads = []
    monkeypatch.setattr(host_spans, "_trace_dir", lambda: str(tmp_path))

    def load(trace_dir, host_names):
        loads.append((trace_dir, set(host_names)))
        return _record()
    monkeypatch.setattr(host_spans.trace_reduce, "load", load)
    return loads


def test_read_loads_once_per_run_and_prints_its_line(found_trace, capsys):
    ctx = _ctx(spans=[("engine.preamble", {"rows": 3})])
    red = host_spans.read(ctx)
    assert host_spans.read(ctx) is red and len(found_trace) == 1
    assert found_trace[0][1] == {"chipbench.window", "chipbench.solve",
                                 "engine.preamble"}
    (line,) = capsys.readouterr().out.splitlines()
    assert line.startswith("chipbench: host_spans ")
    assert '"engine.preamble"' in line


@pytest.mark.parametrize("ctx", [
    _ctx(busy_s=0.0),                 # no device ran (a run on the CPU)
    _ctx(window_s=0.2),               # another run's window
    {"counters": {}, "spans": []},    # untraced
], ids=["no_device", "other_window", "untraced"])
def test_read_finds_nothing(found_trace, ctx):
    assert host_spans.read(ctx) is None


def test_read_without_a_trace_directory(monkeypatch):
    monkeypatch.setattr(host_spans, "_trace_dir", lambda: None)
    assert host_spans.read(_ctx()) is None


def test_trace_directory_is_the_newest_of_the_harness_prefix(monkeypatch,
                                                             tmp_path):
    import os
    monkeypatch.setattr(host_spans.tempfile, "gettempdir",
                        lambda: str(tmp_path))
    assert host_spans._trace_dir() is None
    (tmp_path / "other-dir").mkdir()
    old = tmp_path / "chipbench-trace-a"
    new = tmp_path / "chipbench-trace-b"
    old.mkdir()
    new.mkdir()
    os.utime(old, (1, 1))
    assert host_spans._trace_dir() == str(new)


def _reader(metric):
    return load_module(harness.BENCH_DIR / "metrics" / f"{metric}.py").read


@pytest.mark.parametrize("metric,expect", [
    ("host_preamble_ms.sweep", 0.011 / 2 * 1e3),
    ("host_preamble_ms.solve", 0.011 / 2 * 1e3),
    ("refine_residual_ms", 0.005 / 2 * 1e3),
])
def test_host_span_readers(found_trace, metric, expect):
    read = _reader(metric)
    assert read(_ctx()) == pytest.approx(expect)
    assert read(_ctx(calls=0)) is None
    ctx = _ctx()                      # the parent: the span is not there
    ctx["host_spans"] = {"window_s": 0.1, "host_s": {}}
    assert read(ctx) is None
