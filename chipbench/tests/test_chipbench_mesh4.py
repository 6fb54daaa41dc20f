"""The four-chip cell `pcg.mesh4` at 16 x 16 on 4 forced CPU devices,
through the harness's own functions, and its readers on hand-made trace
records.  The harness runs in one subprocess (the device count is fixed
when JAX starts), with the platform check taking the CPU, as the
`off_chip` fixture does in-process."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from chipbench import harness, load_module, trace_reduce, work

ROOT = Path(__file__).resolve().parents[2]
CELL = "pcg.mesh4"
READERS = ["pcg_iter_device_ms.mesh4", "pcg_exchange_ms.mesh4",
           "pcg_sharded_roofline", "device_idle_share.mesh4",
           "schedule_steps.mesh4", "schedule_mb_per_chip.mesh4"]

SCRIPT = textwrap.dedent("""
    import os
    import sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    from chipbench import harness
    load = harness.load_cell

    def tiny(name):
        cell = load(name)
        return harness.Cell(name, cell.bench, cell.workload,
                            dict(cell.config, nx=16, ny=16))

    harness.REQUIRED_PLATFORM = "cpu"
    harness._enable_compile_cache = lambda: None
    harness.load_cell = tiny
    out = {"untraced": harness.run("pcg.mesh4", 2**31 + 11, 0.2, False),
           "traced": harness.run("pcg.mesh4", 2**31 + 12, 0.2, True)}
    cell = tiny("pcg.mesh4")
    st = cell.driver.setup(cell.config, cell.workload["traffic"], 3,
                           harness.Phases())
    cell.driver.call(st, 0)
    out["setup_counters"] = dict(cell.driver.counters(st),
                                 answers=len(st.answers))
    out["work"] = cell.driver.work(st)
    out["control"] = harness.run_control("pcg.mesh4", 1, 1)
    jax.clear_caches()
    from repro.solver import distributed
    distributed._step_update = lambda x, carry, *a, **k: (x, carry)
    out["step_unchanged"] = harness.run("pcg.mesh4", 5, 0.2, False)
    sys.stdout.write("RESULT " + json.dumps(out) + "\\n")
""")


@pytest.fixture(scope="module")
def mesh4():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{ROOT}{os.pathsep}{ROOT / 'src'}")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(x for x in reversed(proc.stdout.splitlines())
                if x.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_untraced_run_is_correct_and_reports_time_to_tolerance(mesh4):
    got = mesh4["untraced"]
    assert got["correct"] is True and got["failed"] == 0
    assert set(got["metrics"]) == {"pcg_time_to_tol_s", "setup_s"}
    assert got["device"]["count"] == 4
    assert set(got["checks"]) == {"resid", "hist_gap", "failed_calls"}


def test_traced_run_counts_the_capped_call_and_the_schedule(mesh4):
    got = mesh4["traced"]
    assert got["correct"] is True
    # the CPU has no device trace: only the program's counters are read
    assert set(got["metrics"]) == {"schedule_steps.mesh4",
                                   "schedule_mb_per_chip.mesh4"}
    steps = mesh4["setup_counters"]["exchanges"]
    assert got["metrics"]["schedule_steps.mesh4"]["value"] == steps > 0


def test_setup_counts_steps_tiles_and_iterations(mesh4):
    counted = mesh4["setup_counters"]
    assert counted["tile_bytes_per_device"] > 0
    assert len(counted["iterations"]) == 1 and counted["iterations"][0] > 2
    # the warm-up's full solve and the call's, both compared
    assert counted["answers"] == 2
    assert mesh4["work"]["chips"] == 4


def test_control_and_a_broken_step_are_not_correct(mesh4):
    correct, checks = mesh4["control"]
    assert correct is False
    assert mesh4["step_unchanged"]["correct"] is False


def test_capped_call_runs_only_when_tracing(monkeypatch):
    """A traced run's first `trace_calls` calls run the capped executable
    and record its iterations, never an answer; untraced calls do not."""
    from repro import obs
    cell = harness.load_cell(CELL)
    drv = cell.driver
    st = drv.State()
    st.traffic = dict(cell.workload["traffic"])
    st.order, st.rhs, st.b_dev = [0], [None], [None]
    st.A_op = st.M = None
    st.answers, st.iterations = [], []

    class Res:
        iterations = 2

    st.solve_capped = lambda *a: Res()
    st.solve = lambda *a: pytest.fail("the full solve ran")
    obs.enable()
    try:
        drv.call(st, 0)
    finally:
        obs.disable()
    assert st.iterations == [2] and st.answers == []
    with pytest.raises(pytest.fail.Exception):
        drv.call(st, 0)


def test_a_program_with_closure_operands_is_refused_at_once(monkeypatch):
    import jax
    from repro.iterative import operators
    from repro.solver.distributed import default_mesh
    drv = harness.load_cell(CELL).driver
    monkeypatch.setattr(operators, "device_matvec",
                        lambda A, mesh=None, axis="model": (lambda x: x))
    with pytest.raises(RuntimeError, match="jit arguments"):
        drv._require_arguments_form(
            default_mesh(devices=jax.devices()[:1]), "model")


def _record():
    """Two devices, 10 ms window; device 0 busy 6 ms of which 2 ms in
    collectives, device 1 busy 4 ms of which 1 ms."""
    ms = 1_000_000
    return {"devices": {
        "/device:TPU:0": [["fusion.1", 0, 4 * ms],
                          ["all-reduce.3", 4 * ms, ms // 2],
                          ["psum.9", 4 * ms + ms // 2, ms // 2],
                          ["all-gather-start.2", 5 * ms, ms // 2],
                          ["all-gather-done.2", 5 * ms + ms // 2, ms // 2]],
        "/device:TPU:1": [["fusion.1", 0, 3 * ms],
                          ["all-reduce.3", 3 * ms, 1 * ms]]},
        "host": [["chipbench.window", 0, 10 * ms],
                 ["chipbench.solve", 0, 10 * ms]]}


def _ctx(record, iterations=2, counters=None):
    red = trace_reduce.reduce(record)
    peak = work.peaks("TPU v5 lite")
    job = dict(work.pcg_iteration(1000, 4960, 2980), chips=4)
    return {"trace": red, "peak": peak, "work": job, "spans": [],
            "counters": dict({"traced_iterations": iterations},
                             **(counters or {}))}, job, peak


def _read(name, ctx):
    return load_module(harness.BENCH_DIR / "metrics" / f"{name}.py").read(
        ctx)


def test_readers_on_a_hand_made_record():
    ctx, job, peak = _ctx(_record(), counters={
        "exchanges": 92, "tile_bytes_per_device": 2_500_000})
    # busy averaged over the devices: (6 + 4) / 2 = 5 ms, 2.5 per iteration
    assert _read("pcg_iter_device_ms.mesh4", ctx) == pytest.approx(2.5)
    # collectives: (2 + 1) ms over 2 devices and 2 iterations
    assert _read("pcg_exchange_ms.mesh4", ctx) == pytest.approx(0.75)
    assert _read("device_idle_share.mesh4", ctx) == pytest.approx(50.0)
    least, _ = work.least_seconds(job, peak)
    assert _read("pcg_sharded_roofline", ctx) == pytest.approx(
        least / 4 * 2 / 5e-3 * 100)
    assert _read("schedule_steps.mesh4", ctx) == 92
    assert _read("schedule_mb_per_chip.mesh4", ctx) == pytest.approx(2.5)


def test_readers_read_nothing_where_nothing_was_recorded():
    record = _record()
    for evs in record["devices"].values():
        evs[:] = [e for e in evs
                  if not e[0].startswith(("all-", "psum"))]
    ctx, _, _ = _ctx(record)
    assert _read("pcg_exchange_ms.mesh4", ctx) is None
    assert _read("schedule_steps.mesh4", ctx) is None
    assert _read("schedule_mb_per_chip.mesh4", ctx) is None
    empty = {"trace": {"busy_s": 0.0, "window_s": 1.0, "devices": 0,
                       "ops": []},
             "counters": {}, "work": None, "peak": None}
    for name in READERS:
        assert _read(name, empty) is None, name


def test_cell_files_name_four_chips_and_the_readers():
    cell = harness.load_cell(CELL)
    assert cell.chips == cell.config["chips"] == 4
    assert {m["name"] for m in cell.per_layer} == set(READERS)
    assert {m["name"] for m in cell.end_to_end} == {"pcg_time_to_tol_s",
                                                    "setup_s"}
