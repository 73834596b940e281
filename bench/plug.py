"""The modules a configuration file names: its weights builder
(``weights.module``) and its plain reference (``reference``).

A name is a dotted path under ``bench/`` (``bench.reference.dense``), read
from the file it names inside the checkout the cell was loaded from, so a
configuration enters with files of its own and no edit to the harness.
Each file is loaded once a process."""
from __future__ import annotations

import importlib.util
import re
import sys
from pathlib import Path
from types import ModuleType

NAME = re.compile(r"bench(\.[A-Za-z_][A-Za-z0-9_]*)+")


def load(name: str, root: Path) -> ModuleType:
    """The module ``name`` (``bench.a.b``) from ``<root>/bench/a/b.py``."""
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise ValueError(f"{name!r} is not a dotted module path under bench/")
    path = (Path(root) / Path(*name.split("."))).with_suffix(".py")
    if not path.is_file():
        raise FileNotFoundError(f"module {name!r}: no file {path}")
    key = f"bench_plug:{path.resolve()}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]
