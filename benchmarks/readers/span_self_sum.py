"""Per counted step, the summed self time (ms) of the step thread's spans of
``stages``: a span's duration less what its children (the spans of the same
thread that name it as ``parent``) cover, so a wait recorded as a child is
not its parent's work and no time counts twice. A span belongs to the step in
which it began. The step thread is the one that records ``dispatch``
(``idle_under_span.step_thread``). Nothing to read gives ``None``."""

from harness import spec, trace_reduce
from harness.readers import counted_steps, stat


def read(run, args):
    idle = spec.module("readers", "idle_under_span")
    thread = idle.step_thread(run["spans"])
    if thread is None:
        return None
    mine = [s for s in run["spans"] if s.get("thread_id") == thread]
    children = {}
    for s in mine:
        if s.get("parent") is not None:
            children.setdefault(s["parent"], []).append(idle.interval(s))
    wanted = sorted((idle.interval(s), s["id"]) for s in mine
                    if s["stage"] in args["stages"])
    if not wanted:
        return None
    per_step = []
    for step in counted_steps(run):
        ns = 0
        for (lo, hi), ident in wanted:
            if step["t0"] <= lo < step["t1"]:
                ns += (hi - lo) - trace_reduce.union_ns(
                    trace_reduce.clip(children.get(ident, []), lo, hi))
        per_step.append(ns / 1e6)
    return stat(per_step, args.get("stat", "median"))
