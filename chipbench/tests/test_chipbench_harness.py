"""The harness at tiny sizes on the CPU, through the functions the
command-line entry point calls."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
CELLS = ["lung2.sweep.rhs1", "poisson2d.pcg", "lung2.solve.refined"]


def _run(off_chip, capsys, cell, trace, seed=2**31 + 11):
    capsys.readouterr()
    result = off_chip.run(cell, seed, 0.2, trace)
    captured = capsys.readouterr()
    return result, captured.out.splitlines(), captured.err.splitlines()


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_run_reports_the_cells_end_to_end_metrics(cell, off_chip,
                                                           capsys):
    result, out, err = _run(off_chip, capsys, cell, trace=False)
    last = json.loads(out[-1])
    assert last == result
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"] and list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    wanted = {m["name"] for m in harness.load_cell(cell).end_to_end}
    assert set(last["metrics"]) == wanted
    assert all(v["value"] > 0 for v in last["metrics"].values())
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # every compared number beside its limit, last on standard error
    checks = [line for line in err if line.startswith("check ")]
    assert len(checks) == len(last["checks"]) and err[-len(checks):] == \
        checks
    assert any(line.startswith("chipbench: setup ") for line in out)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(cell, off_chip, capsys):
    result, out, _ = _run(off_chip, capsys, cell, trace=True)
    assert result["correct"] is True
    listed = {m["name"] for m in harness.load_cell(cell).per_layer}
    assert set(result["metrics"]) <= listed
    # the CPU has no device trace: only the program's counters and spans
    counted = {"lung2.sweep.rhs1": {"schedule_steps.sweep"},
               "lung2.solve.refined": {"refine_rounds"},
               "poisson2d.pcg": {"schedule_steps.pcg", "pcg_iterations"}}
    assert set(result["metrics"]) == counted[cell]
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_refined_rounds_are_read_from_the_program_spans(off_chip, capsys):
    result, _, _ = _run(off_chip, capsys, "lung2.solve.refined", trace=True)
    assert result["metrics"]["refine_rounds"]["value"] >= 1


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "lung2.sweep.rhs1", "--seed", "3", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def _no_result(stdout: str) -> bool:
    return not any(line.startswith("{") for line in stdout.splitlines())


def test_cli_exits_nonzero_off_a_tpu():
    proc = _cli(ROOT)
    assert proc.returncode != 0 and _no_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_cli_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(tmp_path)
    assert proc.returncode != 0 and _no_result(proc.stdout)
