"""Seconds during which at least one ``Tracer`` span of ``stages`` was open
on any thread: a wall, so spans side by side count once and a span that
straddles an end counts for its part inside. Between two of the run's
recorded moments (``from``, ``to``: names in ``run["events"]``), where,
unlike the readers that walk ``counted_steps``, it sees what lies between
two of them, which is where a recovery is; without them, the median over
the counted steps. ``scale`` multiplies the seconds.

``minus_children``: each span of ``stages`` counts only outside its own
children (``parent`` is its ``id``) of these stages: a ``ring`` span less
its ``ring_preamble`` is the op on the wire, however many other lanes sit
in a preamble beside it.

``complement``: ``true`` gives the seconds of the interval during which
none was open; ``"stages"`` the seconds during which a span of ``stages``
was open and every open one was inside such a child (every open op
waiting, none on the wire).

A missing moment, or a record that holds no span of any of ``stages`` or,
where they are asked for, of ``minus_children`` (a tree from before them),
gives ``None``."""

from harness import spec, trace_reduce
from harness.readers import counted_steps, stat


def read(run, args):
    if "from" in args:
        lo, hi = (run["events"].get(args[k]) for k in ("from", "to"))
        windows = [(lo, hi)] if None not in (lo, hi) and hi > lo else []
    else:
        windows = [(s["t0"], s["t1"]) for s in counted_steps(run)]
    interval = spec.module("readers", "idle_under_span").interval
    stages = set(args["stages"])
    own = [s for s in run["spans"] if s.get("stage") in stages]
    if not own or not windows:
        return None
    whole = counted = [interval(s) for s in own]
    less = set(args.get("minus_children", ()))
    if less:
        kids = {}
        for s in run["spans"]:
            if s.get("stage") in less:
                kids.setdefault(s.get("parent"), []).append(interval(s))
        if not kids:
            return None
        counted = [part for s in own for part in trace_reduce.gaps(
            kids.get(s["id"], []), *interval(s))]
    complement = args.get("complement", False)

    def wall(lo, hi):
        ns = trace_reduce.union_ns(trace_reduce.clip(counted, lo, hi))
        if complement == "stages":
            return trace_reduce.union_ns(
                trace_reduce.clip(whole, lo, hi)) - ns
        return (hi - lo) - ns if complement else ns

    walls = [wall(lo, hi) for lo, hi in windows]
    return stat(walls) / 1e9 * float(args.get("scale", 1.0))
