"""The device's idle time (ms a step) by what the step thread was doing:
the nanoseconds in which no operation ran on the device **and** the
outermost span open on the step thread was one of ``stages`` (``[]``: no span
was open there). The step thread is the one that records ``dispatch``
(``FTTrainer.train_step``'s); outermost (a span with no ``parent``), so that
time under a child counts for its parent. The window is the longest run of
counted traced steps that follow one another and that the device trace shows
whole (``op_ms_seen.seen_steps``: a program matching ``module`` ran whole
inside each), from the first one's start to the last one's end, so a trace
that ends early is not read as idle. The outermost spans of one thread do not
overlap, so metrics whose ``stages`` partition them, with the ``[]`` one, sum
to the window's idle time exactly. A program whose spans carry no
``thread_id`` gives ``None``."""

from harness import spec, trace_reduce


def step_thread(spans):
    """The ``thread_id`` that records most ``dispatch`` spans, or None
    (the id and not the name: pool threads share names)."""
    ids = [s["thread_id"] for s in spans
           if s.get("stage") == "dispatch"
           and s.get("thread_id") is not None]
    return max(set(ids), key=ids.count) if ids else None


def interval(span):
    t0 = int(span["t0_ns"])
    return t0, t0 + max(int(span["dur_ns"]), 0)


def longest_run(run, module):
    """The longest run of steps the device trace shows whole that are
    neighbours in the leader's list of steps."""
    seen = {id(s) for s in
            spec.module("readers", "op_ms_seen").seen_steps(run, module)}
    best, at = [], []
    for s in run["steps"].get(0, []):
        at = at + [s] if id(s) in seen else []
        if len(at) > len(best):
            best = at
    return best


def overlap_ns(a, b):
    """Nanoseconds that two sorted lists of disjoint intervals share."""
    ns = i = j = 0
    while i < len(a) and j < len(b):
        ns += max(min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]), 0)
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return ns


def window_idle(run, module):
    """``(steps, idle)``: the window's steps and the device's idle
    intervals inside it; kept on the record, which several metrics read."""
    dev = run["device_trace"]
    kept = dev.setdefault("idle_under_span", {})
    if module not in kept:
        steps = longest_run(run, module)
        idle = []
        if steps:
            busy = [(s, e) for ev in dev["planes"].values() for _, s, e in ev]
            idle = trace_reduce.gaps(busy, steps[0]["t0"], steps[-1]["t1"])
        kept[module] = (steps, idle)
    return kept[module]


def read(run, args):
    if run.get("device_trace") is None:
        return None
    thread = step_thread(run["spans"])
    if thread is None:
        return None
    steps, idle = window_idle(run, args["module"])
    if not steps:
        return None
    outermost = [s for s in run["spans"]
                 if s.get("thread_id") == thread
                 and s.get("parent") is None]
    stages = set(args["stages"])
    under = trace_reduce.merge(
        interval(s) for s in outermost
        if not stages or s["stage"] in stages)
    ns = overlap_ns(idle, under)
    if not stages:
        ns = sum(e - s for s, e in idle) - ns
    run.setdefault("notes", []).append(
        f"idle under {sorted(stages) or 'no span'}: {ns / 1e6:.3f} ms of "
        f"{sum(e - s for s, e in idle) / 1e6:.3f} ms idle in {len(steps)} "
        f"steps ({(steps[-1]['t1'] - steps[0]['t0']) / 1e6:.1f} ms)")
    return ns / 1e6 / len(steps)
