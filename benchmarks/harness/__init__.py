"""The benchmark's yardstick: everything here is the benchmark's own and
reads the program only through its public entry points, spans, counters and
kernel names (see PERF.md)."""
