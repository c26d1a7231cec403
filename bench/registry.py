"""The harness's modules, found by name: ``bench/<kind>/<name>.py``.

Three kinds are looked up this way: ``apps`` (an application's plain
reference, named by the configuration's ``application.reference``),
``costs`` (a kernel's operations and bytes, named as the trace names the
kernel) and ``metrics`` (a per-layer reader, named as the metric).  A
root's own ``bench/<kind>/<name>.py`` is used where it has one, else the
harness's: so a new application, kernel or metric arrives as a new file,
as a configuration or a traffic mix does (``bench/run.py::load_cell``).
"""

from __future__ import annotations

import importlib
import importlib.util
from pathlib import Path

PACKAGE = Path(__file__).resolve().parent
ROOT = PACKAGE.parent


def names(kind: str, root: Path = ROOT) -> tuple:
    """Every module name of ``kind``, the harness's and the root's."""
    dirs = {PACKAGE / kind, Path(root) / "bench" / kind}
    return tuple(sorted({p.stem for d in dirs if d.is_dir()
                         for p in d.glob("*.py")
                         if not p.name.startswith("_")}))


def load(kind: str, name: str, root: Path = ROOT):
    """The module ``bench/<kind>/<name>.py``: the root's, else the
    harness's."""
    own = Path(root) / "bench" / kind / f"{name}.py"
    if own.exists() and own.resolve() != PACKAGE / kind / f"{name}.py":
        spec = importlib.util.spec_from_file_location(
            f"bench.{kind}.{name}", own)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    return importlib.import_module(f"bench.{kind}.{name}")
