"""Files the benchmark finds by a name that ``BENCHMARK.json`` or a
configuration gives: a model module (``models/<arch>.py``), a kernel
file (``kernels/<kernel>.py``), a per-layer metric's reader
(``metrics/<metric>.py``).  A later cell adds such files and edits
none."""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path

__all__ = ["load", "load_all"]


def load(kind: str, name: str, root: Path):
    """The module ``<root>/<name>.py``; raises ``FileNotFoundError``
    naming the path where there is none."""
    path = Path(root) / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_all(kind: str, root: Path) -> dict:
    """``{name: module}`` of every ``<root>/<name>.py``, sorted by name."""
    return {p.stem: load(kind, p.stem, root)
            for p in sorted(Path(root).glob("*.py"))}
