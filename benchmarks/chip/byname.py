"""Files of the benchmark found by name: ``<kind>/<name><suffix>``.

Configurations, traffic mixes, traffic patterns, references and metric
readers each sit in a file of their own, so a later cell adds files and
entries and edits none."""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def path(kind: str, name: str, suffix: str) -> Path:
    if not NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    p = HERE / kind / f"{name}{suffix}"
    if not p.is_file():
        raise FileNotFoundError(f"{kind} {name!r}: no file {p}")
    return p


def load_module(kind: str, name: str):
    """``<kind>/<name>.py``, by name."""
    p = path(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
