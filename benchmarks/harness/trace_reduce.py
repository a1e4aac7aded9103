"""From traces and spans to numbers.

Two inputs, one clock. The program's ``Tracer`` spans carry
``time.monotonic_ns`` starts. The profiler's xplane counts nanoseconds from
its own start; a ``TraceAnnotation`` written at a known monotonic time (the
mark) gives the offset once, and everything below is then in monotonic
nanoseconds.

Nothing here imports the program."""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Mapping, Sequence, Tuple

Interval = Tuple[int, int]           # [start_ns, end_ns)
DeviceEvent = Tuple[str, int, int]   # name, start_ns, end_ns

MARK = "bench_clock_mark"
# Lines of a TPU device plane that hold one event per executed operation.
# "XLA Modules" and "Steps" hold enclosing events and would count the same
# time twice.
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)   # one event per executed program


# ------------------------------------------------------------- intervals

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union_ns(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in merge(intervals))


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of [lo, hi) that no interval of ``busy`` covers."""
    out, at = [], lo
    for s, e in merge(clip(busy, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


# ----------------------------------------------------------------- spans

def stage_union_per_step(spans: Iterable[Mapping[str, Any]], stage: str,
                         steps: Sequence[Interval]) -> List[float]:
    """For each step interval, the wall (ms) during which at least one span
    of ``stage`` was open: the union, so that eight buckets waiting side by
    side count once. Empty where the stage never appears."""
    ivs = [(int(s["t0_ns"]), int(s["t0_ns"]) + max(int(s["dur_ns"]), 0))
           for s in spans if s.get("stage") == stage]
    if not ivs:
        return []
    return [union_ns(clip(ivs, lo, hi)) / 1e6 for lo, hi in steps]


def label_gap(gap: Interval, spans: Iterable[Mapping[str, Any]]) -> str:
    """The span stage open for most of ``gap`` on the host, or ``host``
    where no span covers any of it."""
    cover: Dict[str, List[Interval]] = {}
    for s in spans:
        t0 = int(s["t0_ns"])
        iv = clip([(t0, t0 + max(int(s["dur_ns"]), 0))], *gap)
        if iv:
            cover.setdefault(str(s["stage"]), []).extend(iv)
    if not cover:
        return "host"
    return max(cover, key=lambda k: union_ns(cover[k]))


# ---------------------------------------------------------------- xplane

def read_xplane(path: str) -> Dict[str, Any]:
    """``{"devices": {plane name: [DeviceEvent]}, "mark_ns": profile time of
    the mark or None}`` with profile-relative nanoseconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[DeviceEvent]] = {}
    modules: Dict[str, List[DeviceEvent]] = {}
    mark = None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_device and line.name in OP_LINES:
                ev = devices.setdefault(plane.name, [])
                for e in line.events:
                    s = int(e.start_ns)
                    ev.append((e.name, s, s + int(e.duration_ns)))
            elif is_device and line.name in MODULE_LINES:
                ev = modules.setdefault(plane.name, [])
                for e in line.events:
                    s = int(e.start_ns)
                    ev.append((e.name, s, s + int(e.duration_ns)))
            elif not is_device and mark is None:
                for e in line.events:
                    if e.name == MARK:
                        mark = int(e.start_ns)
                        break
    return {"devices": devices, "modules": modules, "mark_ns": mark}


def to_monotonic(trace: Dict[str, Any], mark_monotonic_ns: int,
                 what: str = "devices") -> Dict[str, List[DeviceEvent]]:
    if trace["mark_ns"] is None:
        raise ValueError(f"the trace holds no {MARK!r} annotation: the "
                         f"device and host clocks cannot be aligned")
    off = mark_monotonic_ns - trace["mark_ns"]
    return {name: [(n, s + off, e + off) for n, s, e in ev]
            for name, ev in trace[what].items()}


_OPCODE = re.compile(r"\b([a-z][a-z\-]*)\(")


def short_name(name: str, limit: int = 96) -> str:
    """An XLA op's event name is its whole HLO line. Keep the op's name, its
    opcode and its first result shape: ``attn.3 custom-call bf16[32,4096,128]``."""
    if " = " not in name:
        return name[:limit]
    op, rest = name.split(" = ", 1)
    code = _OPCODE.search(rest)
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    parts = [op.lstrip("%"), code.group(1) if code else "",
             shape.group(0) if shape else ""]
    return " ".join(x for x in parts if x)[:limit]


# ------------------------------------------------------- device numbers

def busy_seconds(devices: Mapping[str, Sequence[DeviceEvent]], lo: int,
                 hi: int) -> float:
    """Seconds of [lo, hi) in which an operation ran, averaged over the
    device planes."""
    if not devices:
        return 0.0
    return sum(union_ns(clip([(s, e) for _, s, e in ev], lo, hi))
               for ev in devices.values()) / len(devices) / 1e9


def top_ops(devices: Mapping[str, Sequence[DeviceEvent]], lo: int, hi: int,
            n: int = 10) -> List[List[Any]]:
    total: Dict[str, int] = {}
    for ev in devices.values():
        for name, s, e in ev:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = short_name(name)
                total[key] = total.get(key, 0) + d
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def kernel_seconds(devices: Mapping[str, Sequence[DeviceEvent]],
                   pattern: str, lo: int, hi: int) -> Tuple[float, int]:
    """Summed device time and count of the events whose name matches
    ``pattern`` (a regular expression, searched) and that lie whole inside
    [lo, hi)."""
    rx = re.compile(pattern)
    ns = count = 0
    for ev in devices.values():
        for name, s, e in ev:
            if s >= lo and e <= hi and rx.search(name):
                ns += e - s
                count += 1
    return ns / 1e9, count


def idle_gaps(devices: Mapping[str, Sequence[DeviceEvent]],
              spans: Sequence[Mapping[str, Any]], lo: int, hi: int,
              n: int = 10) -> List[List[Any]]:
    """The ``n`` longest stretches of [lo, hi) in which no device ran an
    operation, each named by what the host's spans say it was doing."""
    busy = [(s, e) for ev in devices.values() for _, s, e in ev]
    longest = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[label_gap(g, spans), (g[1] - g[0]) / 1e9] for g in longest]
