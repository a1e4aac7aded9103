"""What one jitted call costs the calling thread on the attached device,
by the number and the bytes of the program's outputs (PERF.md, PR 43).

``FTTrainer``'s one-group step is not donated: every call allocates a whole
new state (parameters and optimizer state) before it returns. This times the
call alone (the device is idle when it is made and the result is waited for
outside the stamp), for trees shaped like the benchmark's: 39 leaves
(``mistral-7b`` at depth 1 with adamw) and 315 (``trinity-mini``), at the
cells' bytes and at a tenth of them, not donated and donated.

    chiprun --chips 1 -- python3 scripts/dispatch_cost.py
"""

import json
import statistics
import time

import jax
import jax.numpy as jnp


def measure(leaves: int, gib: float, donate: bool, calls: int = 12) -> dict:
    size = int(gib * 2 ** 30 / 4 / leaves)
    tree = [jnp.zeros((size,), jnp.float32) for _ in range(leaves)]
    step = jax.jit(lambda t: [x + 1 for x in t],
                   donate_argnums=(0,) if donate else ())
    out = jax.block_until_ready(step(tree))
    if donate:
        tree = out
    del out
    walls = []
    for _ in range(calls):
        t0 = time.perf_counter()
        out = step(tree)
        walls.append((time.perf_counter() - t0) * 1e3)
        jax.block_until_ready(out)
        if donate:
            tree = out
        del out
    return {"leaves": leaves, "gib": gib, "donated": donate,
            "call_ms_median": round(statistics.median(walls), 3),
            "call_ms_min": round(min(walls), 3),
            "call_ms_max": round(max(walls), 3)}


def main() -> None:
    device = jax.devices()[0]
    print(json.dumps({"platform": device.platform,
                      "kind": device.device_kind}))
    for leaves, gib in ((39, 5.4), (315, 5.6), (39, 0.54), (315, 0.56),
                        (315, 0.01)):
        for donate in (False, True):
            print(json.dumps(measure(leaves, gib, donate)), flush=True)


if __name__ == "__main__":
    main()
