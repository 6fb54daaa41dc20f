"""Trace reduction: busy union, idle share, self time by operation, and
idle gaps put down to the enclosing host span."""
import json
from pathlib import Path

import pytest

from chipbench import trace_reduce

DATA = Path(__file__).resolve().parent / "data" / "lung2_two_sweeps.json"


def test_recorded_two_sweeps_by_hand():
    rec = json.loads(DATA.read_text())
    red = trace_reduce.reduce(rec)
    # window: the chipbench.window span, 0 .. 23,073,591 ns
    assert red["window_s"] == pytest.approx(23_073_591e-9, rel=1e-12)
    # busy: the top-level operations of each call do not overlap, and the
    # three operations inside each `while` lie within it, so busy is the
    # sum of the top-level durations:
    # call 1: copy.1 1816 + pad 1021 + broadcast 71 + while 4105561
    #         + copy-start 5 + copy-done 807 = 4,109,281
    # call 2: 2056 + 1013 + 72 + 4106752 + 6 + 804 = 4,110,703
    busy = 4_109_281 + 4_110_703
    assert red["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)
    assert red["idle_share"] == pytest.approx(1 - busy / 23_073_591,
                                              rel=1e-12)
    assert red["devices"] == 1 and red["calls"] == 2
    ops = dict(red["ops"])
    # the loop's self time leaves out its body's operations:
    # (4105561 - 342 - 570 - 313) + (4106752 - 342 - 338 - 403)
    assert ops["while"] == pytest.approx((4_104_336 + 4_105_669) * 1e-9)
    assert ops["copy.1"] == pytest.approx((1816 + 2056) * 1e-9)
    assert ops["compare_select_fusion.4"] == pytest.approx((570 + 338)
                                                           * 1e-9)
    assert red["ops"][0][0] == "while"
    assert sum(ops.values()) == pytest.approx(busy * 1e-9)
    # every idle stretch lies inside a call's engine.solve span (the host
    # preamble and the copies), so all idle time is put down to it
    assert red["gaps"] == [["engine.solve",
                            pytest.approx((23_073_591 - busy) * 1e-9)]]


def _record(devices, host):
    return {"devices": devices, "host": host}


def test_gaps_go_to_the_innermost_span_and_between_calls_to_the_window():
    rec = _record(
        {"/device:TPU:0": [["a", 10, 20], ["b", 46, 3], ["c", 53, 17],
                           ["d", 90, 5]]},
        [["chipbench.window", 0, 100],
         ["chipbench.solve", 0, 50], ["engine.solve", 30, 15],
         ["chipbench.solve", 52, 48]])
    red = trace_reduce.reduce(rec)
    # idle [0,10): midpoint 5 in the window and the first call, which
    # start together: the shorter, chipbench.solve; [30,46): midpoint 38
    # inside engine.solve (30..45) within the call: engine.solve;
    # [49,53): midpoint 51 between the calls: the window; [70,90) and
    # [95,100): the second call
    assert dict(red["gaps"]) == pytest.approx({
        "chipbench.solve": (10 + 20 + 5) * 1e-9,
        "engine.solve": 16e-9, "chipbench.window": 4e-9})
    assert red["busy_s"] == pytest.approx((20 + 3 + 17 + 5) * 1e-9)
    assert red["calls"] == 2


def test_clipping_overlap_and_devices_averaged():
    rec = _record(
        {"/device:TPU:0": [["x", -10, 30], ["y", 15, 10]],   # overlap
         "/device:TPU:1": [["x", 50, 100]],                   # clipped
         "/device:TPU:2": [["z", 200, 5]]},                   # outside
        [["chipbench.window", 0, 100]])
    red = trace_reduce.reduce(rec)
    # device 0: union [0,25) = 25; device 1: [50,100) = 50; device 2 ran
    # nothing in the window and is not counted
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((25 + 50) / 2 * 1e-9)
    # self time: y overlaps x's tail and is counted inside it
    ops = dict(red["ops"])
    assert ops["y"] == pytest.approx(10e-9)
    assert ops["x"] == pytest.approx((20 - 10 + 50) * 1e-9)


def test_missing_window_raises():
    with pytest.raises(ValueError):
        trace_reduce.reduce(_record({}, [["chipbench.solve", 0, 1]]))


def test_load_reads_host_spans_of_a_recorded_xplane(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda v: v * 2.0)
    x = jnp.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        with jax.profiler.TraceAnnotation("chipbench.solve"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    rec = trace_reduce.load(str(tmp_path), {"chipbench.window",
                                            "chipbench.solve"})
    names = sorted(h[0] for h in rec["host"])
    assert names == ["chipbench.solve", "chipbench.window"]
    win = [h for h in rec["host"] if h[0] == "chipbench.window"][0]
    call = [h for h in rec["host"] if h[0] == "chipbench.solve"][0]
    assert win[1] <= call[1] and call[1] + call[2] <= win[1] + win[2]
    # the CPU backend has no device plane: nothing is busy
    assert trace_reduce.reduce(rec)["busy_s"] == 0.0
