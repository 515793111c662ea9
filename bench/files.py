"""Find a benchmark part by name: ``bench/<kind>/<name>.py``."""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def function(kind: str, name: str, attr: str):
    """The function ``attr`` of ``bench/<kind>/<name>.py``."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)
