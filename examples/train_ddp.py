"""Fault-tolerant data-parallel training example.

The canonical trainer, mirroring the reference example
(/root/reference/train_ddp.py): N replica groups train ResNet-18 on
CIFAR-10-shaped data, surviving whole-group deaths with at most one lost
step. Run one process per replica group:

    # terminal 0 — the global quorum server
    python -m torchft_tpu.lighthouse --bind 0.0.0.0:29510 --min-replicas 1

    # terminal k — one replica group each
    REPLICA_GROUP_ID=k NUM_REPLICA_GROUPS=2 \
    TORCHFT_LIGHTHOUSE=localhost:29510 python examples/train_ddp.py

Kill any trainer mid-run and restart it: it rejoins the quorum, heals the
live weights from a healthy peer over HTTP, and continues — watch the
lighthouse dashboard (http://localhost:29510/) while you do.

Uses synthetic CIFAR-shaped data so the example runs hermetically; swap
``make_dataset`` for a real loader in production. The training loop itself
is the point: quorum, healing, membership-proportional gradient averaging,
and the commit gate are all hidden inside ``FTTrainer``.
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
from torchft_tpu.data import BatchIterator, DistributedSampler
from torchft_tpu.models import ResNet18
from torchft_tpu.parallel import FTTrainer
from torchft_tpu.utils import enable_compile_cache

logging.basicConfig(level=logging.INFO)
logger = logging.getLogger("train_ddp")


def make_dataset(n: int = 4096):
    rng = np.random.default_rng(0)
    return {
        "x": rng.normal(size=(n, 32, 32, 3)).astype(np.float32),
        "y": rng.integers(0, 10, size=(n,)).astype(np.int32),
    }


def main() -> None:
    enable_compile_cache()
    replica_group = int(os.environ.get("REPLICA_GROUP_ID", 0))
    num_groups = int(os.environ.get("NUM_REPLICA_GROUPS", 2))
    total_steps = int(os.environ.get("TOTAL_STEPS", 200))
    batch_size = int(os.environ.get("BATCH_SIZE", 64))
    # OVERLAP_STEPS=1 opts into the cross-step overlap engine: step N's
    # cross-group allreduce drains under step N+1's forward/backward and
    # commits at the N+1 boundary — one-step-stale gradients for comm
    # hidden behind compute (docs/design/overlap.md; enable when the
    # exchange, not the compute, bounds step time). Must match across
    # groups.
    overlap = int(os.environ.get("OVERLAP_STEPS", 0))

    # Self-contained single-group mode: with no TORCHFT_LIGHTHOUSE and
    # only one group, embed the quorum server instead of requiring the
    # operator to start one (multi-group runs must share one).
    embedded_lh = None
    if "TORCHFT_LIGHTHOUSE" not in os.environ and num_groups == 1:
        from torchft_tpu import Lighthouse
        embedded_lh = Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                                 join_timeout_ms=200, quorum_tick_ms=20)
        os.environ["TORCHFT_LIGHTHOUSE"] = embedded_lh.address()
        logger.info("embedded lighthouse at %s", embedded_lh.address())

    data = make_dataset()
    sampler = DistributedSampler(
        dataset_size=len(data["y"]),
        replica_group=replica_group,
        num_replica_groups=num_groups,
        batch_size=batch_size,
        seed=0,
    )
    batches = BatchIterator(data, sampler)

    model = ResNet18(num_classes=10)

    def loss_fn(params, model_state, batch):
        logits, new_state = model.apply(
            {"params": params, **model_state}, batch["x"], train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()
        return loss, new_state

    variables = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)),
                           train=True)

    trainer = FTTrainer(
        loss_fn=loss_fn,
        tx=optax.sgd(0.1, momentum=0.9),
        params=variables["params"],
        model_state={"batch_stats": variables["batch_stats"]},
        manager_factory=lambda load, save: Manager(
            comm=HostCommunicator(),
            load_state_dict=load,
            state_dict=save,
            min_replica_size=1,
            replica_id=f"train_ddp_{replica_group}",
            overlap_steps=overlap,
        ),
    )
    m = trainer.manager
    logger.info("replica group %d/%d up (%s)", replica_group, num_groups,
                m.replica_id())

    t0 = time.perf_counter()
    while m.current_step() < total_steps:
        batch = next(batches)
        loss, committed = trainer.train_step(batch)
        if m.current_step() % 10 == 0:
            dt = time.perf_counter() - t0
            logger.info(
                "step=%d loss=%.4f committed=%s participants=%d "
                "batches_committed=%d (%.2f steps/s)",
                m.current_step(), float(loss), committed,
                m.num_participants(), m.batches_committed(),
                10 / dt if dt else 0)
            t0 = time.perf_counter()

    logger.info("done: %d steps, %d batches committed",
                m.current_step(), m.batches_committed())
    trainer.shutdown()
    if embedded_lh is not None:
        embedded_lh.shutdown()


if __name__ == "__main__":
    main()
