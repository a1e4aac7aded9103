"""Per-layer metrics: ``metrics/<metric>.json`` names a reader by ``kind``
with its arguments, and the reader is the file ``readers/<kind>.py`` with one
function ``read(run, args)``. It takes the run's record and returns one
number, or ``None`` where it finds nothing to read (the metric is then left
out of the line). A later PR adds a metric by adding its file, and a new way
of reading by adding a reader's file.

Here: what the readers share."""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Mapping, Optional

from . import spec


def counted_steps(run: Mapping[str, Any], group: int = 0
                  ) -> List[Dict[str, Any]]:
    """The window steps that count: committed at full membership."""
    return [s for s in run["steps"].get(group, [])
            if s["phase"] == "window" and s["committed"]
            and s["world"] == run["groups"]]


def traced_steps(run: Mapping[str, Any]) -> List[Dict[str, Any]]:
    """The counted steps that lie whole inside the profiler's trace."""
    dev = run["device_trace"]
    return [s for s in counted_steps(run)
            if s["t0"] >= dev["lo"] and s["t1"] <= dev["hi"]]


def stat(values: List[float], which: str = "median") -> Optional[float]:
    if not values:
        return None
    if which == "median":
        return statistics.median(values)
    raise ValueError(f"unknown stat {which!r}")


def read(run: Mapping[str, Any], reader: Mapping[str, Any]
         ) -> Optional[float]:
    return spec.module("readers", reader["kind"]).read(run, reader)
