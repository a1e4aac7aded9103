"""Seconds a step of the driver's plain jitted loop took (the same model,
optimizer and batch with no Manager), run after a traced window."""

from harness.readers import stat


def read(run, args):
    v = stat(run.get("raw_walls") or [], args.get("stat", "median"))
    return None if v is None else v * float(args.get("scale", 1.0))
