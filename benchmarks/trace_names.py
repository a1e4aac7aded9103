#!/usr/bin/env python3
"""Look at one trace by hand: the planes, lines and most expensive event names
of an ``.xplane.pb`` file, so that a metric's name pattern is written against
what the profiler really calls a kernel.

    python3 benchmarks/trace_names.py <file.xplane.pb> [top]
"""

from __future__ import annotations

import sys


def main(argv: list) -> int:
    from jax.profiler import ProfileData

    top = int(argv[2]) if len(argv) > 2 else 25
    data = ProfileData.from_file(argv[1])
    for plane in data.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            total: dict = {}
            count = 0
            for e in line.events:
                count += 1
                t = total.setdefault(e.name, [0, 0.0])
                t[0] += 1
                t[1] += e.duration_ns
            print(f"  LINE {line.name}: {count} events, "
                  f"{len(total)} names")
            if not plane.name.startswith("/device:"):
                continue
            for name, (n, ns) in sorted(total.items(),
                                        key=lambda kv: -kv[1][1])[:top]:
                print(f"    {ns / 1e6:12.3f} ms {n:7d} x  {name[:120]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
