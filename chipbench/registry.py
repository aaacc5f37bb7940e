"""Finds the benchmark's parts by name.  Each part is a file of its own,
``chipbench/<folder>/<name>.py``: a unit of work (``units/``, named by a
traffic file's ``"unit"``), a fabric as the program builds it
(``fabrics/``) or as the reference lowers it (``reference/fabrics/``), both
named by a configuration's ``fabric.kind``, and a per-layer metric's reader
(``metrics/``, named as in `BENCHMARK.json`).  A new one is a new file;
no existing file changes."""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
_loaded: dict = {}


def load(folder: str, name: str):
    """The module ``chipbench/<folder>/<name>.py``, loaded once."""
    key = (folder, name)
    if key not in _loaded:
        path = os.path.join(HERE, folder, name + ".py")
        if not os.path.isfile(path):
            raise ValueError(f"no {folder} part named {name!r} ({path})")
        mod_name = "chipbench_" + "_".join(
            "".join(ch if ch.isalnum() else "_" for ch in part)
            for part in (folder, name))
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[key] = mod
    return _loaded[key]
