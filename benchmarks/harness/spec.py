"""Finds everything a cell is made of by the names in ``BENCHMARK.json`` and
in the cell's own data files. Nothing is listed in code.

Data, each a file ``<sub>/<name>.json`` under one of ``paths``:

- ``configs``: a configuration (by the ``file`` its entry gives); it names its
  ``builder``;
- ``traffic/<mix>``: a traffic mix; it names its ``driver`` and each of its
  ``events`` names its ``kind``;
- ``metrics/<metric>``: a per-layer metric; its ``reader`` names its ``kind``.

Code that belongs to one family of models, one kind of event, one reader, one
kernel or one kind of job, each a file ``<sub>/<name>.py`` under one of
``paths``, loaded by name:

- ``models/<builder>``: the program's loss function at a configuration's
  sizes, the plain reference beside it, parameter shapes, operation counts;
- ``events/<kind>``: what happens to a job at a protocol point;
- ``readers/<kind>``: how a per-layer metric is taken from a run's record;
- ``kernels/<name>``: a kernel's operations and bytes from shapes;
- ``drivers/<driver>``: how a job of that kind is built, stepped and recorded.

So a later PR adds a model family, an event, a reader, a kernel or a cell by
adding files and entries; no file that is here needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)

_roots: List[str] = [BENCH_DIR]      # the directories of ``paths``, absolute
_modules: Dict[str, ModuleType] = {}


def _load(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def configure(root: str) -> Dict[str, Any]:
    """Read ``BENCHMARK.json`` at ``root`` and search its ``paths`` from now
    on. Returns the file's contents."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    _roots[:] = [os.path.join(root, p) for p in bench["paths"]]
    _modules.clear()
    return bench


def find(sub: str, name: str, ext: str) -> str:
    """The one file ``<sub>/<name><ext>`` under the benchmark's ``paths``."""
    tried = [os.path.join(r, sub, name + ext) for r in _roots]
    found = [p for p in tried if os.path.isfile(p)]
    if not found:
        raise FileNotFoundError(
            f"no {sub}/{name}{ext} under the benchmark's paths; add the file "
            f"(looked for {tried})")
    return found[0]


def data(sub: str, name: str) -> Any:
    return _load(find(sub, name, ".json"))


def module(sub: str, name: str) -> ModuleType:
    """``<sub>/<name>.py``, imported once."""
    key = f"{sub}/{name}"
    if key not in _modules:
        path = find(sub, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{sub}_{name}".replace("-", "_").replace(".", "_"), path)
        assert spec is not None and spec.loader is not None
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[key] = mod
    return _modules[key]


def model_of(config: Dict[str, Any]) -> ModuleType:
    if "builder" not in config:
        raise KeyError("the configuration's file names no 'builder' "
                       "(a file models/<builder>.py)")
    return module("models", config["builder"])


class Cell:
    """One entry of ``workloads`` with everything its names point at."""

    def __init__(self, name: str, root: str = REPO_ROOT) -> None:
        bench = configure(root)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"it has {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.entry["config"]]
        self.config = _load(os.path.join(root, cfg_entry["file"]))
        self.mix = data("traffic", self.entry["traffic"])

        def mine(m: Dict[str, Any]) -> bool:
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end: List[Dict[str, Any]] = [
            m for m in bench["end_to_end"] if mine(m)]
        self.per_layer: List[Dict[str, Any]] = [
            {**m, "reader": data("metrics", m["name"])["reader"]}
            for m in bench["per_layer"] if mine(m)]
