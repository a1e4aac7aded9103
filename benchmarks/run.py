#!/usr/bin/env python3
"""Runs one cell of the benchmark and prints its result line last.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Every call is a new process: it builds the cell named in ``BENCHMARK.json``
from ``benchmarks/configs``, ``benchmarks/traffic`` and ``benchmarks/metrics``,
makes weights and data from ``--seed``, warms up every program the window
uses (set-up), measures whole steps for ``--seconds``, checks the outputs
against the plain reference and exits. Without a TPU it exits non-zero and
prints no result line, unless ``--rehearse``: tiny widths on the CPU with
interpreted kernels, which reports ``platform: cpu`` and is never a device
number.

Where the mix gives every replica group a process and a chip of its own, the
process that was started holds the lighthouse, starts one child per group
(this file again, with ``--group``) and never initialises a JAX backend.
"""

from __future__ import annotations

import time

T_PROCESS_NS = time.monotonic_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
for p in (REPO_ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec  # noqa: E402
from harness.spec import Cell  # noqa: E402

# The oracle donates what it can; what it cannot is no news.
import warnings  # noqa: E402

warnings.filterwarnings("ignore", message="Some donated buffers")

KNOWN_TPU_KINDS = ("TPU v5 lite", "TPU v5e")
STALL_FACTOR = 3.0    # a stalled step: over three times the median wall


def log(msg: str) -> None:
    sys.stdout.write(msg + "\n")
    sys.stdout.flush()


# ------------------------------------------------------------- end to end

def step_walls(steps: List[Dict[str, Any]], groups: int
               ) -> List[float]:
    """Seconds of every counted window step (committed at full membership),
    each from the end of the step before it — so glue between steps is
    somebody's time and the walls of a healthy window add up to it."""
    walls, prev = [], None
    for s in steps:
        if s["phase"] != "window":
            continue
        start = s["t0"] if prev is None else prev
        prev = s["t1"]
        if s["committed"] and s["world"] == groups:
            walls.append((s["t1"] - start) / 1e9)
    return walls


def stalled_steps(walls: List[float]) -> int:
    """How many walls are over ``STALL_FACTOR`` times the median wall. No
    metric reads it: ``tokens_per_s`` keeps a stall as somebody's time, and
    the count lets a reader of the ledger tell a stalled run from a slow
    tree."""
    if not walls:
        return 0
    limit = STALL_FACTOR * statistics.median(walls)
    return sum(w > limit for w in walls)


def end_to_end(run: Dict[str, Any]) -> Dict[str, Optional[float]]:
    walls = step_walls(run["steps"].get(0, []), run["groups"])
    ev = run["events"]
    out: Dict[str, Optional[float]] = {
        "tokens_per_s": None, "step_mean_ms": None, "step_p95_ms": None,
        "recover_s": None, "setup_s": None}
    if walls:
        tokens = len(walls) * run["groups"] * run["batch"] * run["seq"]
        out["tokens_per_s"] = tokens / sum(walls) / run["chips"]
        out["step_mean_ms"] = 1e3 * sum(walls) / len(walls)
        ranked = sorted(walls)
        out["step_p95_ms"] = 1e3 * ranked[
            max(0, math.ceil(0.95 * len(ranked)) - 1)]
    if "window.kill" in ev and "window.recovered" in ev:
        out["recover_s"] = (ev["window.recovered"] - ev["window.kill"]) / 1e9
    if "window.t0" in ev:
        out["setup_s"] = (ev["window.t0"] - run["t_process_ns"]) / 1e9
    return out


# ------------------------------------------------------------ correctness

def load_limits(cell: Cell) -> Dict[str, float]:
    with open(os.path.join(BENCH_DIR, "limits.json")) as f:
        limits = {k: float(v["limit"]) for k, v in json.load(f).items()}
    limits.update({k: float(v) for k, v in
                   cell.config.get("limits", {}).items()})
    return limits


def judge(run: Dict[str, Any], limits: Dict[str, float],
          digests: Dict[str, Any], compiled_in_window: int,
          errors: List[str]) -> Dict[str, Any]:
    """``correct`` and every number it rests on beside its limit."""
    window = [s for s in run["steps"].get(0, []) if s["phase"] == "window"]
    aborted = [s for s in window if not s["committed"]]
    spans = [spec.module("events", e["kind"]).designed_abort_window(
        e, run["events"]) for e in run["events_spec"]]
    designed = [s for s in aborted
                if any(w is not None and s["t1"] >= w[0] and s["t0"] <= w[1]
                       for w in spans)]
    lines, ok = [], not errors
    compared: Dict[str, Dict[str, Optional[float]]] = {}
    for e in errors:
        lines.append(f"error: {e}")

    def compare(name: str, value: Optional[float], limit: float) -> None:
        nonlocal ok
        good = value is not None and value <= limit
        ok = ok and good
        compared[name] = {"value": value, "limit": limit}
        lines.append(f"check {name}: {value} (limit {limit}) "
                     f"{'ok' if good else 'NOT CORRECT'}")

    compare("aborted_steps_outside_the_designed_failure",
            float(len(aborted) - len(designed)), 0.0)
    compare("aborted_steps_of_the_designed_failure", float(len(designed)),
            limits["designed_aborts"] * len(spans))
    differ = sorted(g for g, d in digests.items()
                    if d != digests[min(digests)]) if digests else ["none"]
    compare("groups_not_bitwise_equal_to_group_0", float(len(differ)), 0.0)
    compare("programs_compiled_in_window", float(compiled_in_window), 0.0)
    for name in ("state_vs_oracle", "grad_vs_reference"):
        compare(name, run["checks"].get(name), limits[name])
    lines.append(f"info loss_vs_reference: "
                 f"{run['checks'].get('loss_vs_reference')} (no limit: at "
                 f"seeded weights the loss is ln(vocab) in any precision, so "
                 f"no control fails on it)")
    if spans:
        compare("recoveries_missing",
                float(sum(w is None for w in spans)), 0.0)
    return {"correct": bool(ok), "attempted": len(window),
            "failed": len(aborted), "lines": lines, "compared": compared}


# ----------------------------------------------------- one process's part

def check_devices(rehearse: bool, want: int) -> List[Any]:
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if rehearse:
        if d0.platform != "cpu":
            raise SystemExit(f"--rehearse runs on the CPU, found {d0.platform}")
        return devices[:want]
    if d0.platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices() is {len(devices)} x "
                         f"{d0.platform}; the benchmark never falls back")
    if len(devices) < want:
        raise SystemExit(f"the cell asks for {want} chip(s) here, "
                         f"jax.devices() has {len(devices)}")
    if d0.device_kind not in KNOWN_TPU_KINDS:
        raise SystemExit(f"unknown device_kind {d0.device_kind!r}")
    return devices


def stable_cache_keys() -> None:
    """Keep the Python call path out of the compile cache's key. A Mosaic
    kernel's body is serialised into its custom call with its locations
    (``jax/_src/tpu_custom_call.py``: ``enable_debug_info=True``), which carry
    the innermost frames of the trace's traceback (ten by default), and
    ``strip-debuginfo`` cannot reach inside that string: so the same program
    traced through another call path (a replacement's first step inside the
    window, after the rehearsal's in set-up) had another key and compiled
    again, 10 s of ``recover_s`` in a checkout's first run (PERF.md Findings,
    PR 26). With one frame a location the key depends on the kernel's own
    source lines only. (Switching full tracebacks off does the same to the
    key, but XLA then names the kernels ``tpu_custom_call`` and drops the
    scope from every ``op_name``, which ``flash_roofline`` finds them by.)"""
    import jax

    jax.config.update("jax_traceback_in_locations_limit", 1)


def host_part(args: argparse.Namespace, cell: Cell, groups_here: List[int],
              sync_dir: str, lighthouse_addr: str) -> Dict[str, Any]:
    """Run the groups this process holds; return what it found, ready to be
    merged with the other processes' parts."""
    if args.rehearse:
        from torchft_tpu.utils import force_cpu_devices

        force_cpu_devices(1)
    from torchft_tpu.utils import enable_compile_cache

    devices = check_devices(args.rehearse, 1)
    log(f"compile cache: {enable_compile_cache()}")
    stable_cache_keys()

    from harness import readers, trace_reduce

    driver = spec.module("drivers", cell.mix["driver"])
    driver.T_PROCESS_NS = args.t_process
    host = driver.Host(cell, args.seed, args.seconds, bool(args.trace),
                       args.rehearse, groups_here, driver.Sync(sync_dir),
                       lighthouse_addr)
    rec = host.run()
    d0 = devices[0]
    part: Dict[str, Any] = {
        "groups_here": groups_here, "errors": rec["errors"],
        "digests": {str(g): d for g, d in rec["digests"].items()},
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": rec.get("memory_peak_bytes") or 0},
        "compiled_in_window": 0, "read_in_window": 0,
        "replacement_compiled": 0,
    }
    if "compiles_begin" in rec and "compiles_end" in rec:
        (c0, r0), (c1, r1) = rec["compiles_begin"], rec["compiles_end"]
        by_thread = {t: n - c0.get(t, 0) for t, n in c1.items()
                     if n - c0.get(t, 0)}
        # What an event's own threads compile inside the window (a
        # replacement is a new trainer) is part of what the recovery costs,
        # and is counted apart.
        victims = set().union(*(
            spec.module("events", e["kind"]).own_threads(e)
            for e in cell.mix["events"]))
        part["replacement_compiled"] = sum(
            n for t, n in by_thread.items() if t in victims)
        part["compiled_in_window"] = sum(
            n for t, n in by_thread.items() if t not in victims)
        part["read_in_window"] = r1 - r0
    if 0 not in groups_here:
        return part

    run: Dict[str, Any] = {
        **rec, "groups": host.n_groups, "batch": host.batch,
        "seq": host.seq, "cfg": host.cfg, "chips": cell.chips,
        "t_process_ns": args.t_process, "events_spec": cell.mix["events"],
        "groups_on_device": len(groups_here),
        "device_kind": d0.device_kind if d0.platform == "tpu" else None}
    e2e = end_to_end(run)
    run["tokens_per_s"] = e2e["tokens_per_s"]
    part["run"] = {k: run[k] for k in ("steps", "events", "checks")}
    part["shape"] = {k: run[k] for k in ("groups", "batch", "seq", "chips")}
    part["end_to_end"] = e2e
    if args.trace and host.profile is not None and \
            host.profile.get("xplane"):
        trace = trace_reduce.read_xplane(host.profile["xplane"])
        planes = trace_reduce.to_monotonic(trace, host.profile["mark"]) \
            if trace["mark_ns"] is not None else {}
        lo, hi = host.profile["lo"], host.profile["hi"]
        modules = trace_reduce.to_monotonic(
            trace, host.profile["mark"], "modules") if planes else {}
        run["device_trace"] = {"planes": planes, "modules": modules,
                               "lo": lo, "hi": hi}
        # A rate for utilisation that the profiler did not slow: the steps
        # after it stopped, where the window goes on long enough.
        after = [s for s in rec["steps"].get(0, [])
                 if s["t0"] >= host.profile["stopped"]]
        if len(after) >= 10:
            run["tokens_per_s_untraced"] = end_to_end(
                {**run, "steps": {0: after}})["tokens_per_s"]
        part["device"]["busy_s"] = trace_reduce.busy_seconds(planes, lo, hi)
        part["device"]["window_s"] = (hi - lo) / 1e9
        part["breakdown"] = {
            "device_ops": trace_reduce.top_ops(planes, lo, hi),
            "idle_gaps": trace_reduce.idle_gaps(planes, rec["spans"], lo, hi)}
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(host.profile["xplane"], args.keep_trace)
    if host._trace_dir:
        shutil.rmtree(host._trace_dir, ignore_errors=True)
    if args.trace:
        per_layer = {}
        for m in cell.per_layer:
            value = readers.read(run, m["reader"])
            if value is not None:
                per_layer[m["name"]] = {"value": value, "unit": m["unit"]}
        part["per_layer"] = per_layer
        part["notes"] = run.get("notes", [])
    return part


# ------------------------------------------------------------ the result

def finish(args: argparse.Namespace, cell: Cell, parts: List[Dict[str, Any]]
           ) -> int:
    lead = next((p for p in parts if 0 in p["groups_here"]), None)
    errors = [e for p in parts for e in p["errors"]]
    if lead is None or "run" not in lead:
        for e in errors:
            log(f"error: {e}")
        log("no result: the leading group did not finish")
        return 1
    digests = {g: d for p in parts for g, d in p["digests"].items()}
    if len(digests) != lead["shape"]["groups"]:
        errors.append(f"parameters of {len(digests)} of "
                      f"{lead['shape']['groups']} groups were read")
    compiled = sum(p["compiled_in_window"] for p in parts)
    log(f"programs in the window: {compiled} compiled, "
        f"{sum(p['replacement_compiled'] for p in parts)} more compiled by "
        f"a replacement, {sum(p['read_in_window'] for p in parts)} read "
        f"from the cache")
    run = {**lead["run"], "groups": lead["shape"]["groups"],
           "events_spec": cell.mix["events"]}
    run["steps"] = {int(g): s for g, s in run["steps"].items()}  # JSON keys
    verdict = judge(run, load_limits(cell), digests, compiled, errors)
    for note in lead.get("notes", []):
        log(f"note: {note}")
    walls = step_walls(run["steps"].get(0, []), run["groups"])
    counts = {"window_joint_steps": len(walls),
              "stalled_steps": stalled_steps(walls)}
    log("window joint walls (s): " + " ".join(f"{w:.3f}" for w in walls))
    device = dict(lead["device"])
    device["count"] = sum(p["device"]["count"] for p in parts)
    device["memory_peak_bytes"] = max(
        p["device"]["memory_peak_bytes"] for p in parts)
    if args.trace:
        metrics = lead.get("per_layer", {})
    else:
        metrics = {m["name"]: {"value": lead["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end
                   if lead["end_to_end"].get(m["name"]) is not None}
    log("end to end: " + json.dumps(lead["end_to_end"]))
    result = {"correct": verdict["correct"],
              "attempted": verdict["attempted"],
              "failed": verdict["failed"], "metrics": metrics,
              "device": device, **counts}
    if args.trace and "breakdown" in lead:
        result["breakdown"] = lead["breakdown"]
    result["compared"] = verdict["compared"]    # last in the line
    if device["platform"] != "tpu" and not args.rehearse:
        log("no result: not a TPU")
        return 1
    for line in verdict["lines"]:               # last on standard error
        log(line)
        sys.stderr.write(line + "\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


# ------------------------------------------- a process per replica group

def free_port() -> int:
    import socket

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def one_chip_env(k: int) -> Dict[str, str]:
    """Chip ``k`` of the host as a process's whole topology (the spelling of
    ``chip_smoke.two_chip_env``, for one chip)."""
    port = free_port()
    return {"TPU_VISIBLE_CHIPS": str(k),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
            "TPU_PROCESS_PORT": str(port), "CLOUD_TPU_TASK_ID": "0",
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}


def orchestrate(args: argparse.Namespace, cell: Cell, sync_dir: str,
                lighthouse_addr: str) -> List[Dict[str, Any]]:
    """Start one child per group; pass on what they print; return their
    parts. The first child that fails, or the time limit, stops the rest."""
    n = int(cell.mix["groups"])
    parts: List[Optional[Dict[str, Any]]] = [None] * n
    procs, readers_ = [], []

    def read(k: int, proc: subprocess.Popen) -> None:
        assert proc.stdout is not None
        for line in proc.stdout:
            if line.startswith("PART "):
                parts[k] = json.loads(line[len("PART "):])
            else:
                log(f"[group {k}] " + line.rstrip("\n"))

    try:
        for k in range(n):
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace",
                   str(args.trace if k == 0 else 0), "--root", args.root,
                   "--group", str(k), "--sync-dir", sync_dir,
                   "--lighthouse", lighthouse_addr,
                   "--t-process", str(args.t_process)]
            if args.rehearse:
                cmd.append("--rehearse")
            if args.keep_trace:
                cmd += ["--keep-trace", args.keep_trace]
            if args.override:
                cmd += ["--override", args.override]
            env = {**os.environ, **({"JAX_PLATFORMS": "cpu"} if args.rehearse
                                    else one_chip_env(k))}
            proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                    text=True)
            procs.append(proc)
            t = threading.Thread(target=read, args=(k, proc), daemon=True)
            t.start()
            readers_.append(t)
        deadline = time.monotonic() + 1100   # a first run compiles
        while any(p.poll() is None for p in procs):
            if any(p.poll() not in (None, 0) for p in procs):
                time.sleep(3)   # let the others say why, too
                break
            if time.monotonic() > deadline:
                log("children did not end in time")
                break
            time.sleep(0.05)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()
        for t in readers_:
            t.join(10)
    if any(p.returncode != 0 for p in procs) or any(x is None for x in parts):
        raise SystemExit("a replica group's process failed")
    return [x for x in parts if x is not None]


# --------------------------------------------------------------------- main

def start_with(env: Dict[str, str]) -> None:
    """Start this process anew, by its own command line, with ``env`` in its
    environment, where it does not have it yet. A traffic mix's ``env`` is
    what an operator sets for the job's process, and some of it (the C
    library's allocator reads ``MALLOC_*`` before ``main``) only a process
    started with it obeys. The clock of ``setup_s`` keeps running: the new
    start is told when the first one began."""
    if all(os.environ.get(k) == v for k, v in env.items()):
        return
    os.environ.update(env)
    sys.stdout.flush()
    os.execv(sys.executable,
             [*sys.orig_argv, "--t-process", str(T_PROCESS_NS)])


def main(argv: Optional[List[str]] = None, may_restart: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny widths, interpreted kernels; reports "
                         "platform cpu and is never a device number")
    ap.add_argument("--root", default=REPO_ROOT,
                    help="where BENCHMARK.json and its paths are read from")
    ap.add_argument("--override", default=None,
                    help="JSON merged into the traffic mix: for the controls "
                         "that must come out as not correct, never for a "
                         "measurement")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's xplane file to this directory")
    # How a process-per-group cell starts its groups; not for users.
    ap.add_argument("--group", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--sync-dir", help=argparse.SUPPRESS)
    ap.add_argument("--lighthouse", help=argparse.SUPPRESS)
    ap.add_argument("--t-process", type=int, default=T_PROCESS_NS,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    cell = Cell(args.workload, args.root)
    if args.override:
        cell.mix.update(json.loads(args.override))
        log(f"CONTROL RUN, not a measurement: mix overridden with "
            f"{args.override}")
    mix = cell.mix
    env = {k: str(v) for k, v in mix.get("env", {}).items()}
    if may_restart and args.group is None:
        start_with(env)
    os.environ.update(env)
    if args.trace:
        # The Tracer keeps 64 steps of spans by default; a traced window
        # reads all of its steps' spans at the end.
        os.environ.setdefault("TORCHFT_TRACE_STEPS", "100000")

    if args.group is not None:          # a child of orchestrate()
        part = host_part(args, cell, [args.group], args.sync_dir,
                         args.lighthouse)
        sys.stderr.flush()
        print("PART " + json.dumps(part), flush=True)
        return 0 if not part["errors"] else 1

    sync_dir = tempfile.mkdtemp(prefix="bench_sync_")
    lighthouse = spec.module("drivers", mix["driver"]).make_lighthouse(mix)
    try:
        if mix.get("process_per_group"):
            parts = orchestrate(args, cell, sync_dir, lighthouse.address())
        else:
            parts = [host_part(args, cell, list(range(int(mix["groups"]))),
                               sync_dir, lighthouse.address())]
    finally:
        lighthouse.shutdown()
        shutil.rmtree(sync_dir, ignore_errors=True)
    return finish(args, cell, parts)


if __name__ == "__main__":
    sys.exit(main(may_restart=True))
