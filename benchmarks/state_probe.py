#!/usr/bin/env python3
"""Runs one cell as ``run.py`` does, in this process, and writes what its
steps and threads did to a JSON file: the evidence of ``PERF.md``'s Findings
on the multi-group cells' states (PR 36). Never a measurement: the probe's
own thread and samples are in the run.

    python3 benchmarks/state_probe.py OUT.json [--switch-interval S] -- \\
        --workload mistral-7b.steady-2g --seed 3 --seconds 45

What the file holds, from the benchmark's side only (the program's spans and
counters, ``/proc/self``, the process's clocks):

- ``steps``: every step of every group this process holds, warm-up too
  (``t0``, ``t1``, ``phase``, ``committed``, ``world``, the trainer's
  ``timings``), the leader's with the process's CPU ticks at its end;
- ``spans``: ``[stage initial, t0_ns, dur_ns, lane]`` of the ``ring``,
  ``fetch_wait`` and ``put`` spans the tracer still holds (64 steps);
- ``samples``: at the window's begin and end, every thread's name, CPU
  ticks, and the C allocator's ``mallinfo2``;
- ``lock_probe_us``: by how much a thread that sleeps 2 ms comes back late
  inside the window, which includes its wait for the interpreter lock;
- ``counters`` (``Manager.metrics()`` at the window's begin and end),
  ``events``, ``end_to_end``, the environment that matters and the machine.

``--switch-interval`` sets ``sys.setswitchinterval``: a diagnostic for waits
on the interpreter lock, not a setting of any cell.
"""

from __future__ import annotations

import ctypes
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(BENCH_DIR), BENCH_DIR]

ENV_PREFIXES = ("MALLOC_", "GLIBC_", "TORCHFT_", "XLA_", "TPU_", "JAX_",
                "LIBTPU", "PYTHON")


class _Mallinfo2(ctypes.Structure):
    _fields_ = [(n, ctypes.c_size_t) for n in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]


def mallinfo() -> Dict[str, Any]:
    try:
        libc = ctypes.CDLL("libc.so.6")
        libc.mallinfo2.restype = _Mallinfo2
        info = libc.mallinfo2()
    except (OSError, AttributeError) as e:
        return {"error": repr(e)}
    return {n: getattr(info, n) for n, _ in _Mallinfo2._fields_}


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return ""


def _stat_fields(text: str) -> List[str]:
    """The fields of a ``/proc/.../stat`` line from the third on."""
    return text[text.rindex(")") + 2:].split()


def process_ticks() -> Dict[str, int]:
    f = _stat_fields(_read("/proc/self/stat"))
    return {"minflt": int(f[7]), "utime": int(f[11]), "stime": int(f[12])}


def threads() -> List[Dict[str, Any]]:
    names = {t.native_id: t.name for t in threading.enumerate()}
    out = []
    for tid in os.listdir("/proc/self/task"):
        text = _read(f"/proc/self/task/{tid}/stat")
        if not text:
            continue        # the thread ended meanwhile
        f = _stat_fields(text)
        out.append({"tid": int(tid), "py": names.get(int(tid)),
                    "comm": text[text.index("(") + 1:text.rindex(")")],
                    "minflt": int(f[7]), "utime": int(f[11]),
                    "stime": int(f[12]), "cpu": int(f[36])})
    return out


def main() -> int:
    split = sys.argv.index("--")
    mine, argv = sys.argv[1:split], sys.argv[split + 1:]
    out_path = mine[0]
    if "--switch-interval" in mine:
        sys.setswitchinterval(
            float(mine[mine.index("--switch-interval") + 1]))

    import run as bench
    from harness import spec

    found: Dict[str, Any] = {
        "argv": argv, "samples": {},
        "switch_interval": sys.getswitchinterval(),
        "env": {k: v for k, v in os.environ.items()
                if k.startswith(ENV_PREFIXES)},
        "machine": {"cpus": os.cpu_count(),
                    "affinity": sorted(os.sched_getaffinity(0)),
                    "model": [ln for ln in _read("/proc/cpuinfo").splitlines()
                              if ln.startswith("model name")][:1]}}
    late: List[Any] = []
    stop = threading.Event()

    def lock_probe() -> None:
        while not stop.is_set():
            t = time.monotonic_ns()
            time.sleep(0.002)
            now = time.monotonic_ns()
            late.append((now, now - t - 2_000_000))

    def sample(name: str) -> None:
        found["samples"][name] = {
            "t_ns": time.monotonic_ns(), "threads": threads(),
            "mallinfo": mallinfo(), "process": process_ticks()}

    host_part, end_to_end = bench.host_part, bench.end_to_end

    def probed_host_part(args: Any, cell: Any, *rest: Any) -> Any:
        host = spec.module("drivers", cell.mix["driver"]).Host
        begins, ends, step = host._window_begins, host._window_ends, host.step

        def window_begins(self: Any, trainer: Any) -> None:
            sample("window_begin")
            begins(self, trainer)

        def window_ends(self: Any, trainer: Any) -> None:
            ends(self, trainer)
            sample("window_end")

        def one_step(self: Any, gi: int, *a: Any) -> Dict[str, Any]:
            rec = step(self, gi, *a)
            if gi == 0:
                rec["process"] = process_ticks()
            return rec

        host._window_begins, host._window_ends = window_begins, window_ends
        host.step = one_step
        threading.Thread(target=lock_probe, daemon=True,
                         name="lock-probe").start()
        return host_part(args, cell, *rest)

    def probed_end_to_end(run: Dict[str, Any]) -> Dict[str, Any]:
        e2e = end_to_end(run)
        if "counters" in run and "steps" not in found:
            stop.set()
            lo = run["events"].get("window.t0", 0)
            hi = run["events"].get("window.t1", 0)
            inside = sorted(x for t, x in late if lo <= t <= hi)
            if inside:
                found["lock_probe_us"] = {
                    "n": len(inside),
                    "mean": sum(inside) / len(inside) / 1e3,
                    **{f"p{q}": inside[len(inside) * q // 100] / 1e3
                       for q in (50, 90, 99)}}
            found.update(
                steps=run["steps"], events=run["events"],
                counters=run["counters"], end_to_end=e2e,
                spans=[[s["stage"][0], s["t0_ns"], s["dur_ns"],
                        s.get("lane", -1)] for s in run.get("spans", [])
                       if s.get("stage") in ("ring", "fetch_wait", "put")])
            os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                        exist_ok=True)
            with open(out_path, "w") as f:
                json.dump(found, f)
        return e2e

    bench.host_part, bench.end_to_end = probed_host_part, probed_end_to_end
    # No restart here: the probe's process is the job's. A mix's MALLOC_*
    # has to be in the environment the probe is started with.
    return bench.main(argv)


if __name__ == "__main__":
    sys.exit(main())
