"""A plain copy over two of a host's four TPU chips that halts the cores.

Found by PR 25 while bringing `chip_smoke.py --chips 4` phase (b) up on a
v5e 2x2 host (jax 0.9.0, libtpu 0.0.34): a program that runs on a strict
subset of the host's chips can die with

    Core halted unexpectedly ... schecklt: Invalid logical z:
    enhanced-barrier-parent-phase-1

and take the process with it. No torchft code is involved:

    python scripts/tpu_submesh_repro.py 23 32000   # chips 2,3, 500 MiB: halts
    python scripts/tpu_submesh_repro.py 23 256     # 4 MiB: runs
    python scripts/tpu_submesh_repro.py 01 32000   # chips 0,1: runs
    python scripts/tpu_submesh_repro.py 13 32000   # ran alone; the same
                                                   # pairing halted inside
                                                   # chip_smoke (b)

Run it again after a libtpu upgrade: two replica groups of two chips each
in ONE process cannot pass on a runtime where this halts. Needs four
attached TPU chips; each case in a process of its own.

The other half of the question — do two chips work as a process's WHOLE
topology — is answered by `chip_smoke.py --chips 4` (b): one process per
replica group, each given its chips by `TPU_VISIBLE_CHIPS`, passes.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main() -> None:
    ids = [int(c) for c in sys.argv[1]]
    rows = int(sys.argv[2])
    devs = jax.devices()
    assert devs[0].platform == "tpu" and len(devs) == 4, devs
    mesh = Mesh(np.array([devs[i] for i in ids]), ("fsdp",))
    x = jax.device_put(np.ones((rows, 4096), np.float32),
                       NamedSharding(mesh, P("fsdp", None)))
    jax.block_until_ready(x)
    print("placed on chips", ids, flush=True)
    jax.block_until_ready(jnp.copy(x))
    print("copied: no halt", flush=True)


if __name__ == "__main__":
    main()
