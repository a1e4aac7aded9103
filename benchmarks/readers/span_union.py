"""Per counted step, the wall (ms) during which at least one ``Tracer`` span
of a stage was open: a wall, not a sum of overlapping spans."""

from harness import trace_reduce
from harness.readers import counted_steps, stat


def read(run, args):
    steps = [(s["t0"], s["t1"]) for s in counted_steps(run)]
    per_step = trace_reduce.stage_union_per_step(
        run["spans"], args["stage"], steps)
    return stat(per_step, args.get("stat", "median"))
