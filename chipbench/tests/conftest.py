"""The benchmark's own tests run on the CPU, at tiny sizes.

`tests/conftest.py` of the program does not reach this directory, so the
platform is set here, and the checkout's root and `src/` are put on the
path the way `chipbench/run.py` puts them."""
import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIGS = ROOT / "chipbench" / "configs"


@pytest.fixture
def tiny_configs():
    """Each configuration at a size a test holds: lung2 at 2% of its rows
    (same generator, same level structure), Poisson on a 16 x 16 grid."""
    lung = json.loads((CONFIGS / "lung2_full.json").read_text())
    lung["scale"] = 0.02
    poisson = json.loads((CONFIGS / "poisson2d_512_ic0.json").read_text())
    poisson["nx"] = poisson["ny"] = 16
    return {"lung2_full": lung, "poisson2d_512_ic0": poisson}


@pytest.fixture
def off_chip(monkeypatch, tiny_configs):
    """The harness, run through its own functions on the CPU at tiny
    sizes: the platform check takes the CPU, JAX's compilation cache is
    left as the test session has it, and every cell loads its
    configuration at the tiny size."""
    from chipbench import harness
    load = harness.load_cell

    def tiny(name):
        cell = load(name)
        return harness.Cell(name, cell.bench, cell.workload,
                            tiny_configs[cell.workload["config"]])

    monkeypatch.setattr(harness, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(harness, "_enable_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "load_cell", tiny)
    return harness
