"""On-chip benchmark of the SpTRSV solve path (see `run.py`).

Drivers, metric readers and matrix generators are files found by name
(`drivers/<driver>.py`, `metrics/<metric>.py`, `generators/<name>.py`);
`load_module` loads one."""
import importlib.util
from pathlib import Path


def load_module(path: Path):
    """The Python file at `path` as a module of its own, named after its
    directory and file (a file name may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
