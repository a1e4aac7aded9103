"""``memory_stats()["peak_bytes_in_use"]`` after the window, largest over the
cell's devices. Sound because a process runs one cell."""


def read(run, args):
    v = run.get("memory_peak_bytes")
    return None if not v else v * float(args.get("scale", 1.0))
