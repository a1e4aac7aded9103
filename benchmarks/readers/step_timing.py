"""A key of ``FTTrainer.last_step_timings`` over the counted steps."""

from harness.readers import counted_steps, stat


def read(run, args):
    vals = [s["timings"][args["key"]] for s in counted_steps(run)
            if args["key"] in s["timings"]]
    v = stat(vals, args.get("stat", "median"))
    return None if v is None else v * float(args.get("scale", 1.0))
