"""Fault-tolerant training on REAL TPU pod slices — the multi-host trainer.

Topology (SURVEY.md §7: replica group = TPU slice):

    lighthouse (any CPU VM)          <- global quorum arbiter
      ├─ replica group 0 = slice 0   <- N hosts, one process per host
      │    host 0: Manager(rank=0) hosts the group's manager + store
      │    host k: Manager(rank=k) joins the same quorum/commit barriers
      └─ replica group 1 = slice 1   ...

Within a slice, the model is sharded over ALL the slice's chips with a
``jax.sharding.Mesh`` (dp × fsdp here) — XLA emits the ICI collectives, the
framework never sees them. Across slices, gradients ride the resizable
:class:`HostCommunicator` ring over DCN, one ring per local-rank stratum
(store prefix ``.../torchft/{quorum_id}/{rank}``), which is what makes
membership changes per-step instead of stop-the-world (the reference's DDP
comm-hook allreduce plays this role, /root/reference/torchft/ddp.py:47-65).

Run — see docs/pod_runbook.md for the full drill. Single process (laptop /
CI / one-host slice) degenerates to exactly train_ddp.py behavior:

    python examples/train_pod.py

Real pod, e.g. 2 × v5e-16 (4 hosts per slice), per host of slice S:

    TORCHFT_LIGHTHOUSE=<lighthouse-vm>:29510 \
    REPLICA_GROUP_ID=S NUM_REPLICA_GROUPS=2 \
    TORCHFT_NUM_PROCESSES=4 TORCHFT_PROCESS_ID=<this host 0..3> \
    TORCHFT_COORDINATOR=<slice-S host-0 ip>:8476 \
    TORCHFT_STORE_ADDR=<slice-S host-0 ip>:29511 \
    python examples/train_pod.py

Kill ANY slice (all its hosts) mid-run and restart it: the survivors keep
training (fast eviction cuts the quorum in ~heartbeat-staleness, not the
join timeout), and the restarted slice heals the live sharded weights from
a healthy peer — each restored leaf is ``device_put`` straight onto its
fsdp sharding.
"""

from __future__ import annotations

import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchft_tpu import HostCommunicator, Manager
from torchft_tpu._native import Store
from torchft_tpu.data import DistributedSampler
from torchft_tpu.models import MLP
from torchft_tpu.parallel import FTTrainer, make_mesh
from torchft_tpu.parallel.sharding import batch_spec, combined_shardings
from torchft_tpu.utils import enable_compile_cache

logging.basicConfig(level=logging.INFO)
logger = logging.getLogger("train_pod")


def main() -> None:
    enable_compile_cache()
    # ---------------------------------------------------------- topology
    replica_group = int(os.environ.get("REPLICA_GROUP_ID", 0))
    num_groups = int(os.environ.get("NUM_REPLICA_GROUPS", 1))
    num_processes = int(os.environ.get("TORCHFT_NUM_PROCESSES", 1))
    process_id = int(os.environ.get("TORCHFT_PROCESS_ID", 0))
    total_steps = int(os.environ.get("TOTAL_STEPS", 100))
    batch_size = int(os.environ.get("BATCH_SIZE", 64))  # per PROCESS
    fsdp = int(os.environ.get("FSDP", 0))  # 0 = infer: all chips on fsdp

    if num_processes > 1:
        # Multi-host slice: every process sees the WHOLE slice's devices
        # after initialize(); jax.local_devices() is this host's chips.
        jax.distributed.initialize(
            coordinator_address=os.environ["TORCHFT_COORDINATOR"],
            num_processes=num_processes,
            process_id=process_id,
        )

    n_devices = len(jax.devices())
    if fsdp <= 0:
        fsdp = n_devices  # pure-FSDP default: biggest model capacity
    mesh = make_mesh({"dp": -1, "fsdp": fsdp})
    logger.info("group %d/%d process %d/%d: mesh %s over %d devices",
                replica_group, num_groups, process_id, num_processes,
                dict(zip(mesh.axis_names, mesh.devices.shape)), n_devices)

    # ---------------------------------------------------------- lighthouse
    # Degenerate/self-contained mode: no TORCHFT_LIGHTHOUSE and a single
    # replica group means nobody started an external quorum server — embed
    # one (multi-group runs must share one, so there we require the env).
    embedded_lh = None
    if "TORCHFT_LIGHTHOUSE" not in os.environ:
        if num_groups > 1:
            raise SystemExit(
                "TORCHFT_LIGHTHOUSE must point at the shared lighthouse "
                "when NUM_REPLICA_GROUPS > 1 (see docs/pod_runbook.md)")
        from torchft_tpu import Lighthouse
        embedded_lh = Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                                 join_timeout_ms=200, quorum_tick_ms=20)
        os.environ["TORCHFT_LIGHTHOUSE"] = embedded_lh.address()
        logger.info("embedded lighthouse at %s", embedded_lh.address())

    # ---------------------------------------------------------- store
    # Rank 0 hosts the group's KV store on a FIXED port so the other hosts
    # can be pointed at it with TORCHFT_STORE_ADDR (single-process runs let
    # the Manager start an ephemeral one instead).
    store_addr = os.environ.get("TORCHFT_STORE_ADDR")
    store_server = None
    if store_addr and process_id == 0:
        port = store_addr.rsplit(":", 1)[1]
        store_server = Store(bind=f"0.0.0.0:{port}")

    # ---------------------------------------------------------- model
    model = MLP(features=(2048, 2048), num_classes=10)
    rng = np.random.default_rng(0)
    data = {
        "x": rng.normal(size=(8192, 256)).astype(np.float32),
        "y": rng.integers(0, 10, size=(8192,)).astype(np.int32),
    }

    def loss_fn(params, batch):
        logits = model.apply(params, batch["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()

    params = model.init(jax.random.key(0), jnp.zeros((1, 256)))
    shardings = combined_shardings(params, mesh)
    bspec = batch_spec(mesh)
    bshard = jax.sharding.NamedSharding(mesh, bspec)

    # ---------------------------------------------------------- sampler
    # 2D grid: replica groups × processes. Each process loads only its own
    # shard; the global batch is assembled below from per-process data
    # (multi-host jax.Arrays are built from process-local shards).
    sampler = DistributedSampler(
        dataset_size=len(data["y"]),
        replica_group=replica_group,
        num_replica_groups=num_groups,
        rank=process_id,
        num_replicas=num_processes,
        batch_size=batch_size,
        seed=0,
    )
    index_iter = iter(sampler)

    def next_batch():
        nonlocal index_iter
        try:
            idx = next(index_iter)
        except StopIteration:
            sampler.set_epoch(sampler.epoch + 1)
            index_iter = iter(sampler)
            idx = next(index_iter)
        local = {k: v[idx] for k, v in data.items()}
        if num_processes == 1:
            return jax.device_put(local, jax.tree_util.tree_map(
                lambda _: bshard, local))
        # Multi-host: every process contributes its local shard of the
        # global [num_processes * batch_size, ...] array.
        return jax.tree_util.tree_map(
            lambda a: jax.make_array_from_process_local_data(bshard, a),
            local)

    # ---------------------------------------------------------- trainer
    trainer = FTTrainer(
        loss_fn=loss_fn,
        tx=optax.adamw(1e-3),
        params=params,
        param_shardings=shardings,
        manager_factory=lambda load, save: Manager(
            comm=HostCommunicator(),
            load_state_dict=load,
            state_dict=save,
            min_replica_size=1,
            replica_id=f"pod{replica_group}",
            rank=process_id,
            world_size=num_processes,
            store_addr=store_addr,
            # OVERLAP_STEPS=1: hide the cross-group exchange behind the
            # next step's compute (one-step-stale grads; enable when
            # metrics.json shows the step comm-bound — see
            # docs/design/overlap.md and the pod_runbook tuning entry).
            # Must match on every process of every group.
            overlap_steps=int(os.environ.get("OVERLAP_STEPS", 0)),
        ),
    )
    m = trainer.manager
    logger.info("up: %s rank %d/%d (metrics: http://<rank-0 host>:"
                "<manager port>/metrics.json)",
                m.replica_id(), process_id, num_processes)

    t0 = time.perf_counter()
    while m.current_step() < total_steps:
        loss, committed = trainer.train_step(next_batch())
        if m.current_step() % 10 == 0 and process_id == 0:
            dt = time.perf_counter() - t0
            logger.info(
                "step=%d loss=%.4f committed=%s participants=%d "
                "(%.2f steps/s)", m.current_step(), float(loss), committed,
                m.num_participants(), 10 / dt if dt else 0.0)
            t0 = time.perf_counter()

    logger.info("done: %d steps, %d batches committed",
                m.current_step(), m.batches_committed())
    trainer.shutdown()
    if store_server is not None:
        store_server.shutdown()
    if embedded_lh is not None:
        embedded_lh.shutdown()


if __name__ == "__main__":
    main()
