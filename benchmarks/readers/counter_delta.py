"""A key of ``Manager.metrics()`` as its change over the window, optionally
divided by another key's change. ``on``: ``leader`` (group 0's manager,
window begin to window end) or ``replacement`` (the last manager born inside
the window, whose totals are the changes). Where the window's last snapshot
lacks a key (a parent commit from before the counter) there is nothing to
read."""


def _counters(run, on):
    c = run["counters"]
    if on == "leader":
        end = [c[k] for k in c if k.startswith("end.0.")]
        return (c.get("begin.0"), end[0]) if end else None
    if on == "replacement":
        # end.<group>.<life>: a life above 0 is a manager born in the run
        lives = sorted((int(k.split(".")[2]), k) for k in c
                       if k.startswith("end.") and int(k.split(".")[2]) > 0)
        return ({}, c[lives[-1][1]]) if lives else None
    raise ValueError(f"unknown counter owner {on!r}")


def read(run, args):
    pair = _counters(run, args.get("on", "leader"))
    if pair is None or pair[0] is None:
        return None
    begin, end = pair
    if args["key"] not in end or args.get("per", args["key"]) not in end:
        return None

    def delta(key):
        return float(end[key]) - float(begin.get(key, 0.0))

    value = delta(args["key"])
    if "per" in args:
        per = delta(args["per"])
        if per <= 0:
            return None
        value /= per
    elif value == 0.0 and args.get("skip_zero", True):
        return None
    return value * float(args.get("scale", 1.0))
