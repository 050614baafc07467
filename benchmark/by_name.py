"""Files found by name: `benchmark/<kind>/<name>.py`."""
from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(kind: str, name: str):
    """A driver (`kind` "drivers") or a per-layer reader ("layer_metrics"),
    imported from its file. A later PR adds one by adding the file."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
