"""Seconds between two of the run's recorded moments."""


def read(run, args):
    ev = run["events"]
    a, b = ev.get(args["from"]), ev.get(args["to"])
    if a is None or b is None:
        return None
    return (b - a) / 1e9
