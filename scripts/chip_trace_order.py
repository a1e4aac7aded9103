#!/usr/bin/env python3
"""One step's device operations of a kept trace IN THE ORDER THEY RAN, as
JSON lines: the operations of the "XLA Ops" line inside the longest whole
"XLA Modules" event (the step's program), each with its start from the
program's start and its duration in microseconds. What answers "does the
routing run before the attention kernel" (PERF.md, PR 51).

    python3 scripts/chip_trace_order.py <dir with *.xplane.pb> <out.jsonl> [name chars]
"""

import glob
import json
import sys


def main(argv):
    from jax.profiler import ProfileData

    chars = int(argv[3]) if len(argv) > 3 else 200
    path = sorted(glob.glob(argv[1] + "/**/*.xplane.pb", recursive=True))[0]
    data = ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = lines.get("XLA Modules", [])
        if not modules:
            continue
        # a whole step's program from the middle of the trace
        longest = max(e.duration_ns for e in modules)
        whole = [e for e in modules if e.duration_ns > 0.8 * longest]
        step = whole[len(whole) // 2]
        t0, t1 = step.start_ns, step.start_ns + step.duration_ns
        ops = sorted((e for e in lines.get("XLA Ops", [])
                      if t0 <= e.start_ns < t1), key=lambda e: e.start_ns)
        with open(argv[2], "w") as out:
            out.write(json.dumps({"module": step.name[:200],
                                  "ms": step.duration_ns / 1e6,
                                  "ops": len(ops)}) + "\n")
            for i, e in enumerate(ops):
                out.write(json.dumps({
                    "i": i, "at_us": round((e.start_ns - t0) / 1e3, 1),
                    "us": round(e.duration_ns / 1e3, 1),
                    "name": e.name[:chars]}) + "\n")
        return 0
    return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
