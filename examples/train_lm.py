"""Fault-tolerant LLM pretraining: HSDP within each group, FT across groups.

BASELINE.md config 3's shape, end to end: a Llama-recipe decoder whose
parameters shard over the replica group's own device mesh (fsdp × tp —
XLA emits the ICI collectives), while the fault-tolerance manager
replicates training across replica groups (quorum per step, commit vote,
live-weight healing of *sharded* arrays). The reference's equivalent is
DDP + "Hybrid FSDP" composition (/root/reference/torchft/manager.py:23-25,
process_group.py:744-770); here the intra-group story is jit + NamedSharding.

Run (one process per replica group; each sees its own TPU slice or, for a
local demo, a virtual CPU mesh):

    # terminal 0 — quorum server + dashboard
    python -m torchft_tpu.lighthouse --bind 0.0.0.0:29510 --min-replicas 1

    # terminal k ∈ {0, 1}
    REPLICA_GROUP_ID=$k NUM_REPLICA_GROUPS=2 \
    TORCHFT_LIGHTHOUSE=localhost:29510 \
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/train_lm.py

Kill either process mid-run and restart it: it rejoins the quorum, heals
the sharded params/opt-state from the healthy peer (device_put onto its
own mesh), and the groups converge in lockstep.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding

from torchft_tpu import HostCommunicator, Manager, chaos
from torchft_tpu.data import (DistributedSampler, ElasticLoader,
                              ElasticSampler, StatefulLoader,
                              TokenFileDataset)
from torchft_tpu.models import (Transformer, TransformerConfig,
                                chunked_causal_lm_loss, tiny_config,
                                tp_rules)
from torchft_tpu.parallel import (FTTrainer, batch_spec,
                                  combined_shardings, make_mesh)
from torchft_tpu.utils import enable_compile_cache

logging.basicConfig(level=logging.INFO)
logger = logging.getLogger("train_lm")


def make_config() -> TransformerConfig:
    """Size from env; defaults to a demo-scale model that fits anywhere.
    ``MODEL=7b`` is Llama-2 7B with the flash kernel; ``main`` re-wraps
    the kernel for the group's mesh once that exists."""
    if os.environ.get("MODEL", "tiny") == "tiny":
        return tiny_config(max_seq_len=128)
    from torchft_tpu.models import llama2_7b_config
    from torchft_tpu.ops import flash_attention

    return llama2_7b_config(attention_fn=flash_attention, remat=True)


def main() -> None:
    enable_compile_cache()
    replica_group = int(os.environ.get("REPLICA_GROUP_ID", 0))
    num_groups = int(os.environ.get("NUM_REPLICA_GROUPS", 2))
    total_steps = int(os.environ.get("TOTAL_STEPS", 50))
    batch_size = int(os.environ.get("BATCH_SIZE", 8))
    seq_len = int(os.environ.get("SEQ_LEN", 128))
    # OVERLAP_STEPS=1: cross-step overlap engine — step N's cross-group
    # allreduce drains under step N+1's forward/backward, commit deferred
    # to the N+1 boundary (one-step-stale grads; see
    # docs/design/overlap.md for when the trade wins). Must be set
    # identically on every group.
    overlap = int(os.environ.get("OVERLAP_STEPS", 0))

    cfg = make_config()

    # The group's own mesh: shard params over fsdp, projections over tp.
    n_dev = jax.device_count()
    tp = 2 if n_dev % 2 == 0 and cfg.num_heads % 2 == 0 else 1
    mesh = make_mesh({"fsdp": n_dev // tp, "tp": tp})
    logger.info("group %d mesh: %s", replica_group, dict(mesh.shape))
    if cfg.attention_fn is not None:
        # XLA cannot partition a Mosaic kernel: inside the sharded jit it
        # runs under a shard_map over this group's mesh.
        from torchft_tpu.ops import sharded_flash_attention

        cfg = dataclasses.replace(
            cfg, attention_fn=sharded_flash_attention(mesh))
    model = Transformer(cfg)

    # Storage-backed corpus: TOKENS_FILE points at a flat token .npy (your
    # real pretraining data); otherwise a synthetic one is materialized
    # once and memmapped like the real thing. The 2D sampler shards it
    # across replica groups; the StatefulLoader prefetches off the page
    # cache and checkpoints its exact stream position (the torchdata
    # StatefulDataLoader role, reference train_ddp.py:53-57).
    tokens_file = os.environ.get("TOKENS_FILE")
    if not tokens_file:
        tokens_file = os.path.join(
            os.environ.get("DATA_DIR", "/tmp/torchft_tpu_data"),
            f"synth_tokens_v{cfg.vocab_size}.npy")
        if not os.path.exists(tokens_file):
            # Atomic publish: groups on one host share DATA_DIR, and a
            # concurrently starting peer must never memmap a half-written
            # file — write per-group temp, then rename (last one wins,
            # contents identical by the fixed seed).
            rng = np.random.default_rng(0)
            # (.npy suffix so np.save does not append one to the temp name)
            tmp = f"{tokens_file}.{replica_group}.{os.getpid()}.tmp.npy"
            TokenFileDataset.write(
                tmp,
                rng.integers(0, cfg.vocab_size, size=4096 * seq_len)
                .astype(np.uint16 if cfg.vocab_size <= 65536 else np.int32))
            os.replace(tmp, tokens_file)
    dataset = TokenFileDataset(tokens_file, seq_len=seq_len)
    # ELASTIC_DATA=1 swaps the static 2D sampler for the quorum-following
    # elastic stream (ElasticSampler + ElasticLoader): slots re-partition
    # with membership instead of losing a dead group's shard, prefetch is
    # keyed on the commit-predicted next slots, and exact resume is FREE —
    # the stream position IS manager.batches_committed(), which already
    # rides the manager checkpoint state, so no loader state is saved.
    elastic = os.environ.get("ELASTIC_DATA") == "1"
    if elastic:
        batches = None  # built after the trainer (the sampler needs its manager)
    else:
        sampler = DistributedSampler(
            dataset_size=len(dataset),
            replica_group=replica_group,
            num_replica_groups=num_groups,
            batch_size=batch_size,
            seed=0,
        )
        batches = StatefulLoader(dataset, sampler, prefetch=2)

    def loss_fn(params, batch):
        # Chunked loss: the [B, S, vocab] logits tensor (LM training's
        # largest allocation) never materializes — essential at the 7B
        # config's 32k vocab.
        hidden = model.apply(params, batch["tokens"], return_hidden=True)
        return chunked_causal_lm_loss(
            hidden, params["params"]["lm_head"]["kernel"],
            batch["tokens"])

    params = model.init(jax.random.key(0),
                        jnp.zeros((1, seq_len), jnp.int32))
    shardings = combined_shardings(params, mesh, tp_rules())

    trainer = FTTrainer(
        loss_fn=loss_fn,
        tx=optax.adamw(3e-4),
        params=params,
        param_shardings=shardings,
        batch_sharding=NamedSharding(
            mesh, batch_spec(mesh, data_axes=("fsdp",))),
        manager_factory=lambda load, save: Manager(
            # TORCHFT_CHAOS soaks every transport: the ring/store/manager/
            # heal hooks activate inside their clients; the allreduce path
            # needs the explicit shim, so wrap when a schedule is active.
            comm=(chaos.ChaosCommunicator(HostCommunicator())
                  if chaos.active() is not None else HostCommunicator()),
            load_state_dict=load,
            state_dict=save,
            min_replica_size=1,
            replica_id=f"train_lm_{replica_group}",
            overlap_steps=overlap,
        ),
    )
    m = trainer.manager
    if elastic:
        batches = ElasticLoader(
            dataset,
            ElasticSampler(len(dataset), m, batch_size=batch_size, seed=0),
            prefetch=2)
    logger.info("replica group %d/%d up (%s)", replica_group, num_groups,
                m.replica_id())

    # Durable checkpoint/resume (the reference documents the cadence in its
    # trainer, train_ddp.py:130-137: manager state MUST ride with the model
    # state so step counters stay in sync). Live healing covers replica
    # death; this covers whole-job restarts.
    ckpt_dir = os.environ.get("CHECKPOINT_DIR")
    ckpt_every = int(os.environ.get("CHECKPOINT_EVERY", 10))
    # The saved tree's structure differs by data mode (elastic saves no
    # loader state), and checkpoint_io.load matches structure strictly —
    # partition the directory by mode so toggling ELASTIC_DATA against an
    # existing CHECKPOINT_DIR starts a fresh lineage instead of crashing
    # resume on a shape mismatch.
    ckpt_name = f"{replica_group}-elastic" if elastic else str(replica_group)
    if ckpt_dir:
        from torchft_tpu import checkpoint_io

        # recover(), not latest(): the newest file may be torn (crash
        # mid-write on a non-atomic filesystem) or bit-rotted — the scan
        # verifies digests, quarantines bad files, and falls back to the
        # previous good snapshot instead of crashing the trainer.
        path = checkpoint_io.recover(os.path.join(ckpt_dir, ckpt_name))
        if path:
            target = {"trainer": trainer.state_dict()}
            if not elastic:
                target["loader"] = batches.state_dict()
            user, mgr_state = checkpoint_io.load(path, target=target)
            trainer.load_state_dict(user["trainer"])
            if not elastic:  # elastic resume = batches_committed (mgr state)
                batches.load_state_dict(user["loader"])
            m.load_state_dict(mgr_state)
            logger.info("resumed from %s at step %d", path,
                        m.current_step())

    # Async writer: durable saves snapshot on-device in milliseconds and
    # serialize/write on a background thread — the step loop never stalls
    # for the device fetch or the disk (keep=3 retains a rollback window).
    ckpt_writer = None
    if ckpt_dir:
        from torchft_tpu.checkpoint_io import AsyncCheckpointer

        ckpt_writer = AsyncCheckpointer(keep=3)

    # Spot/preemptible reclaim notices (docs/design/churn.md): SIGTERM
    # arms the graceful drain — at the next clean commit boundary the
    # manager farewells the quorum (survivors lose nothing), takes a
    # final durable save (SAME tree structure as the cadence saves, so
    # resume never hits a mismatch), withdraws its heal/publish
    # advertisements, and step() raises PreemptedExit below.
    def _drain_user_state():
        user = {"trainer": trainer.state_dict()}
        if not elastic:
            user["loader"] = batches.state_dict()
        return user

    if ckpt_writer is not None:
        m.set_durable_target(ckpt_writer,
                             os.path.join(ckpt_dir, ckpt_name),
                             user_state_fn=_drain_user_state)
    m.install_preemption_handler()

    from torchft_tpu import PreemptedExit

    t0 = time.perf_counter()
    preempted = False
    while not preempted and m.current_step() < total_steps:
        # Elastic mode hands the loader ITSELF to train_step (a zero-arg
        # callable): the draw then happens after manager.step(), reading
        # the step's true slot.
        batch = batches if elastic else next(batches)
        try:
            loss, committed = trainer.train_step(batch)
        except PreemptedExit:
            # The noticed-reclaim SUCCESS path: the drain already
            # farewelled, took the final save, withdrew advertisements,
            # and shut the manager down — exit 0 before the SIGKILL.
            logger.info("gracefully preempted at step %d; exiting",
                        m.current_step())
            preempted = True
            continue
        step = m.current_step()
        if ckpt_writer is not None and committed and step % ckpt_every == 0:
            # Overlap mode keeps one allreduce in flight across the step
            # boundary; save_durable refuses such mid-flight snapshots
            # (manager metadata and params would describe different
            # steps). Settle it first — costs this one step's overlap,
            # only at checkpoint cadence.
            trainer.flush()
            user = {"trainer": trainer.state_dict()}
            if not elastic:
                user["loader"] = batches.state_dict()
            # Commit-coupled: the manager stamps step + quorum metadata
            # into the file head and refuses to snapshot mid-heal /
            # errored state (checkpoint cadence bounds the gap).
            m.save_durable(ckpt_writer, os.path.join(ckpt_dir, ckpt_name),
                           user_state=user)
        if step % 10 == 0:
            dt = time.perf_counter() - t0
            logger.info(
                "step=%d loss=%.4f committed=%s participants=%d "
                "(%.2f steps/s)",
                step, float(loss), committed,
                m.num_participants(), 10 / dt if dt else 0)
            t0 = time.perf_counter()
    if not preempted:
        logger.info("done: %d steps, %d batches committed",
                    m.current_step(), m.batches_committed())
    try:
        if ckpt_writer is not None:
            ckpt_writer.shutdown()  # drain the in-flight durable save;
            # raises if the final write failed — teardown still runs so
            # the manager farewells the lighthouse cleanly.
    finally:
        # Nested so a loader shutdown failure (ElasticLoader/StatefulLoader
        # raise when a prefetch thread wedges on storage past its join
        # timeout) can never skip trainer.shutdown() — skipping it leaves
        # the quorum thread and checkpoint server running and the
        # lighthouse without a farewell.
        try:
            batches.shutdown()
        finally:
            trainer.shutdown()


if __name__ == "__main__":
    main()
