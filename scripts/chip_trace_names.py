#!/usr/bin/env python3
"""Full event names of a kept device trace, most expensive first, as JSON
lines under chiprun_out/: what a metric's pattern is written against and
what ``benchmarks/tests/*_op_names.json`` pins.

    python3 scripts/chip_trace_names.py <dir with *.xplane.pb> <out.jsonl> [top]
"""

import glob
import json
import sys


def main(argv):
    from jax.profiler import ProfileData

    top = int(argv[3]) if len(argv) > 3 else 80
    path = sorted(glob.glob(argv[1] + "/**/*.xplane.pb", recursive=True))[0]
    data = ProfileData.from_file(path)
    with open(argv[2], "w") as out:
        for plane in data.planes:
            if not plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                total = {}
                for e in line.events:
                    t = total.setdefault(e.name, [0, 0.0])
                    t[0] += 1
                    t[1] += e.duration_ns
                for name, (n, ns) in sorted(total.items(),
                                            key=lambda kv: -kv[1][1])[:top]:
                    out.write(json.dumps({"plane": plane.name,
                                          "line": line.name, "ms": ns / 1e6,
                                          "n": n, "name": name[:1500]}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
