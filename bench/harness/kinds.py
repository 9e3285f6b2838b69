"""Files found by name under the benchmark's directory.

Each kind of thing a cell is made of that may grow is one file per name:

- ``generators/<name>.py``: a configuration's table and pool
  (``make(cfg, column)``);
- ``columns/<kind>.py``: a column kind (``make(spec, rows, rng)`` draws
  the column, ``add(table, name, values)`` adds it to the program's
  ``MMOTable``);
- ``predicates/<kind>.py``: a filter node (``bind``, ``build``, ``mask``;
  see ``harness/predicates.py``);
- ``metrics/<name>.py``: a per-layer metric's reader (``read(record)``).

A later cell that needs a new one adds the file; nothing here or in the
rest of the harness is edited.
"""
from __future__ import annotations

import importlib.util
import os
import re
from types import ModuleType
from typing import Dict, Tuple

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Kinds:
    """The files of one benchmark directory, loaded once each."""

    def __init__(self, bench_dir: str = BENCH):
        self.bench_dir = os.path.abspath(bench_dir)
        self._mods: Dict[Tuple[str, str], ModuleType] = {}

    def path(self, folder: str, name: str) -> str:
        return os.path.join(self.bench_dir, folder, f"{name}.py")

    def has(self, folder: str, name: str) -> bool:
        return os.path.isfile(self.path(folder, name))

    def module(self, folder: str, name: str) -> ModuleType:
        mod = self._mods.get((folder, name))
        if mod is not None:
            return mod
        path = self.path(folder, name)
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no {folder} file {name!r} at {path}")
        tag = re.sub(r"\W", "_", f"{folder}_{name}_{abs(hash(path))}")
        spec = importlib.util.spec_from_file_location(f"bench_{tag}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        self._mods[(folder, name)] = mod
        return mod


DEFAULT = Kinds()
