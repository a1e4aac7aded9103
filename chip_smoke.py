#!/usr/bin/env python3
"""The quickest proof that the fault-tolerant trainer still starts on the chip.

    python chip_smoke.py             # one TPU chip, phases 0-3
    python chip_smoke.py --chips 4   # one host of four: the sharded path only
    python chip_smoke.py --rehearse  # CPU, tiny widths, interpreted kernels

Drives the library's main path — ``FTTrainer`` + ``Manager`` +
``HostCommunicator`` + the in-process native ``Lighthouse``, training a
Llama-recipe decoder with the Pallas flash kernel — through the entry points
a user calls, at the published Llama-2-7B widths (embed 4096, 32 heads of
128, FFN 11008, vocab 32000, 4096-token sequences). Depth is the only cut.

The default run is one process, the only one that touches JAX: the
lighthouse is C++ threads inside it and the replica groups that share the
chip are Python threads. Its phases: 0 device, 1 kernel (flash fwd/bwd
against plain f32 attention, fused against split backward), 2 one replica
group taking five adamw steps at depth 2, 3 two replica groups on the one
chip with a kill and a heal.

``--chips 4`` runs instead (a) one group sharded fsdp=2 × tp=2 over the four
chips against the same steps on one chip, in one process, and then (b) two
groups of two chips with a kill and a heal of the sharded state, one process
per group as a pod runs them. The process that was started holds the
lighthouse, starts those three and never initialises a JAX backend itself,
so a chip belongs to one process at a time.

Any phase that raises ends the run with a non-zero exit code. The last line
of standard output is one JSON object, ``{"ok": ..., "device": {...}}``;
``--rehearse`` never reports a TPU there.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.metadata
import json
import os
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

KNOWN_TPU_KINDS = ("TPU v5 lite", "TPU v5e")

# Tolerances, stated once. Flash attention multiplies in bf16 with f32
# accumulation and rounds the probabilities to bf16 before the PV matmul;
# the reference runs the same bf16-rounded inputs through f32 at the
# highest matmul precision. bf16 carries 8 bits of mantissa (2^-8 ≈ 4e-3
# per rounding), so a few roundings deep the outputs and gradients must
# agree within 2e-2 of the reference's largest magnitude.
KERNEL_TOL = 2e-2
# Four chips against one: same seed, same batch, same arithmetic up to the
# order of the bf16/f32 partial sums that tensor parallelism reorders.
LOSS_TOL = 1e-2
# No chip may hold more than this multiple of an even share of the state.
SHARE_TOL = 1.3


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at. ``FULL`` is the published Llama-2-7B layer;
    ``TINY`` exists only behind ``--rehearse``."""

    widths: Dict[str, int]
    seq: int
    kernel_shape: Tuple[int, int, int, int]
    fused_block: int
    depth: int = 2           # phase 2 and --chips 4 (a)
    ft_depth: int = 1        # phase 3 and --chips 4 (b): two groups' state
    ft_seq: int = 1024       # ... and their sequence length (see phase 3)
    steps: int = 5


FULL = Sizes(widths={}, seq=4096, kernel_shape=(1, 4096, 32, 128),
             fused_block=512)
TINY = Sizes(widths=dict(vocab_size=512, embed_dim=128, num_heads=4,
                         hidden_dim=256, max_seq_len=128),
             seq=128, kernel_shape=(1, 128, 4, 32), fused_block=32,
             ft_seq=64)


class Run:
    """What every phase needs: the sizes, the devices, the seed."""

    def __init__(self, args: argparse.Namespace, devices: list) -> None:
        self.seed: int = args.seed
        self.sizes = TINY if args.rehearse else FULL
        self.devices = devices
        # Compiled on a TPU, always; interpreted only in a rehearsal.
        self.interpret = bool(args.rehearse)


_LOG_LOCK = threading.Lock()


def log(msg: str) -> None:
    # Whole lines: replica groups, and the readers that pass on the parts
    # of --chips 4, write from several threads at once.
    with _LOG_LOCK:
        sys.stdout.write(msg + "\n")
        sys.stdout.flush()


# ------------------------------------------------------------------ helpers

def make_model(run: Run, depth: int, attention_fn: Callable):
    from torchft_tpu.models import (Transformer, chunked_causal_lm_loss,
                                    llama2_7b_config)

    cfg = llama2_7b_config(num_layers=depth, attention_fn=attention_fn,
                           remat=True, **run.sizes.widths)
    model = Transformer(cfg)

    def loss_fn(params, batch):
        hidden = model.apply(params, batch["tokens"], return_hidden=True)
        return chunked_causal_lm_loss(
            hidden, params["params"]["lm_head"]["kernel"], batch["tokens"])

    return cfg, model, loss_fn


def flash_fn(run: Run) -> Callable:
    import functools

    from torchft_tpu.ops import flash_attention

    return functools.partial(flash_attention, interpret=run.interpret)


def init_params(run: Run, model: Any, device: Any) -> Any:
    """Random weights from ``--seed``, made in one jitted program on
    ``device``."""
    import jax
    import jax.numpy as jnp

    with jax.default_device(device):
        return jax.jit(model.init)(
            jax.random.key(run.seed), jnp.zeros((1, run.sizes.seq), jnp.int32))


def make_batch(run: Run, cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    import numpy as np

    rng = np.random.default_rng(run.seed)
    return {"tokens": rng.integers(0, cfg.vocab_size, size=(batch, seq),
                                   dtype=np.int32)}


def make_manager(lighthouse_addr: str, name: str) -> Callable:
    """The ``manager_factory`` of examples/train_lm.py, with the lighthouse's
    address passed in and ``min_replica_size=1`` (a survivor commits
    alone)."""
    from torchft_tpu import HostCommunicator, Manager

    # Timeouts sized for gigabytes of gradients and healed state crossing
    # the host on every step, and for peers that compile for a minute.
    return lambda load, save: Manager(
        comm=HostCommunicator(timeout_sec=600),
        load_state_dict=load, state_dict=save,
        min_replica_size=1, replica_id=name,
        lighthouse_addr=lighthouse_addr, rank=0, world_size=1,
        timeout_ms=600_000, quorum_timeout_ms=600_000)


def n_bytes(tree: Any) -> int:
    import jax

    return sum(int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "nbytes"))


def check_digests(name: str, mx: Dict[str, float]) -> None:
    """docs/design/state_attestation.md: every commit boundary but a
    manager's first is digested; a digest that raised was swallowed and
    counted."""
    want = mx["commit_count"] - 1
    log(f"  {name}: digests {mx['sdc_digests_total']:.0f} of {want:.0f} "
        f"digested boundaries, {mx['sdc_digest_failures']:.0f} failed, "
        f"{mx['sdc_digest_ms_total']:.1f} ms")
    if mx["sdc_digest_failures"] != 0:
        raise AssertionError(f"{name}: a state digest failed on the device")
    if mx["sdc_digests_total"] != want:
        raise AssertionError(
            f"{name}: {mx['sdc_digests_total']} digests for {want} "
            f"digested commit boundaries")


def device_bytes(devices: list, key: str = "peak_bytes_in_use"
                 ) -> List[Optional[int]]:
    """``memory_stats()[key]`` per device; None where the backend reports
    none (the CPU of a rehearsal)."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(stats.get(key) if stats else None)
    return out


# ------------------------------------------------------------------ phase 0

def phase_device(args: argparse.Namespace, want: int) -> list:
    """Exactly the ``want`` devices asked for, or raise. Returns them."""
    import jax
    import jaxlib

    from torchft_tpu.utils import enable_compile_cache

    devices = jax.devices()
    d0 = devices[0]
    if args.rehearse:
        if d0.platform != "cpu" or len(devices) < want:
            raise RuntimeError(
                f"--rehearse needs {want} CPU device(s), found "
                f"{len(devices)} x {d0.platform}")
        devices = devices[:want]
    else:
        if d0.platform != "tpu":
            raise RuntimeError(
                f"no TPU: jax.devices() is {len(devices)} x {d0.platform} "
                f"({d0.device_kind}); this script never falls back")
        if len(devices) != want:
            raise RuntimeError(
                f"asked for {want} TPU chip(s), jax.devices() has "
                f"{len(devices)}")
        if d0.device_kind not in KNOWN_TPU_KINDS:
            raise RuntimeError(
                f"unknown device_kind {d0.device_kind!r}; known: "
                f"{KNOWN_TPU_KINDS}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"device: {len(devices)} x {d0.platform} ({d0.device_kind}); "
        f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}")
    log(f"compile cache: {enable_compile_cache()}")
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        log(f"TPU_VISIBLE_CHIPS={visible}: this process was given those "
            f"chips of the host")
    return devices


# ------------------------------------------------------------------ phase 1

def phase_kernel(run: Run) -> None:
    """flash_attention fwd + dq/dk/dv against plain f32 attention, then the
    fused backward against the split one."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.transformer import plain_attention
    from torchft_tpu.ops import flash_attention
    from torchft_tpu.ops.fused_bwd_check import TOLERANCE, fused_vs_split

    shape = run.sizes.kernel_shape
    kq, kk, kv, kw = jax.random.split(jax.random.key(run.seed), 4)
    q, k, v, w = (jax.random.normal(key, shape, jnp.bfloat16)
                  for key in (kq, kk, kv, kw))

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=run.interpret)
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    # The reference materializes [heads, S, S] f32 scores: a few heads at a
    # time (one compiled program, reused) keeps it to a fraction of HBM.
    def ref_loss(q, k, v, w):
        with jax.default_matmul_precision("highest"):
            out = plain_attention(q.astype(jnp.float32),
                                  k.astype(jnp.float32),
                                  v.astype(jnp.float32), True)
        return jnp.sum(out * w.astype(jnp.float32)), out

    ref = jax.jit(jax.value_and_grad(ref_loss, argnums=(0, 1, 2),
                                     has_aux=True))
    group = min(8, shape[2])
    worst = 0.0
    for h0 in range(0, shape[2], group):
        sl = (slice(None), slice(None), slice(h0, h0 + group))
        (_, r_out), r_grads = ref(q[sl], k[sl], v[sl], w[sl])
        for name, got, want in zip(
                ("out", "dq", "dk", "dv"),
                (out[sl],) + tuple(g[sl] for g in grads),
                (r_out,) + tuple(r_grads)):
            got = got.astype(jnp.float32)
            if not bool(jnp.all(jnp.isfinite(got))):
                raise AssertionError(f"flash {name} is not finite")
            rel = float(jnp.max(jnp.abs(got - want))
                        / jnp.max(jnp.abs(want)))
            worst = max(worst, rel)
            if rel > KERNEL_TOL:
                raise AssertionError(
                    f"flash {name} heads {h0}..{h0 + group}: rel diff "
                    f"{rel:.3e} > {KERNEL_TOL}")
    log(f"  flash {list(shape)} bf16 causal vs plain f32 attention: worst "
        f"rel diff {worst:.3e} (tolerance {KERNEL_TOL})")

    rel = fused_vs_split(shape, block=run.sizes.fused_block,
                         interpret=run.interpret)
    log("  fused vs split backward: " + ", ".join(
        f"{n} {rel[n]:.3e}" for n in ("dq", "dk", "dv"))
        + f" (tolerance {TOLERANCE})"
        + (" [interpreted: both legs run the split kernels]"
           if run.interpret else ""))
    if rel["worst"] > TOLERANCE:
        raise AssertionError(
            f"fused backward differs from split by {rel['worst']:.3e}: "
            f"the fused kernel is not right on this libtpu; set "
            f"TORCHFT_FLASH_FUSED_BWD=0")


# ------------------------------------------------------------------ phase 2

def train_one_group(run: Run, name: str, depth: int,
                    batch_shape: Tuple[int, int], steps: int,
                    mesh: Any = None) -> Dict[str, Any]:
    """One replica group built the way examples/train_lm.py builds it,
    ``steps`` steps on one batch. With ``mesh`` the state is sharded
    fsdp × tp over it; without, everything lives on the first device."""
    import jax
    import optax
    from jax.sharding import NamedSharding

    from torchft_tpu import Lighthouse
    from torchft_tpu.models import tp_rules
    from torchft_tpu.ops import sharded_flash_attention
    from torchft_tpu.parallel import (FTTrainer, batch_spec,
                                      combined_shardings)

    device = run.devices[0]
    attn = (sharded_flash_attention(mesh, interpret=run.interpret)
            if mesh is not None else flash_fn(run))
    cfg, model, loss_fn = make_model(run, depth, attn)
    params = init_params(run, model, device)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    log(f"  {name}: {n_params / 1e6:.1f} M parameters "
        f"({n_bytes(params) / 2**30:.2f} GiB), depth {depth} (the cut), "
        f"batch {batch_shape[0]} x {batch_shape[1]} tokens")
    batch = make_batch(run, cfg, *batch_shape)
    shardings = batch_sharding = None
    if mesh is not None:
        shardings = combined_shardings(params, mesh, tp_rules())
        batch_sharding = NamedSharding(
            mesh, batch_spec(mesh, data_axes=("fsdp",)))
    lh = Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                    join_timeout_ms=100, quorum_tick_ms=10)
    trainer = None
    try:
        with jax.default_device(device):
            trainer = FTTrainer(
                loss_fn=loss_fn, tx=optax.adamw(3e-4), params=params,
                param_shardings=shardings, batch_sharding=batch_sharding,
                manager_factory=make_manager(lh.address(), name))
            del params  # the trainer has its own copy
            losses, walls = [], []
            for _ in range(steps):
                t0 = time.perf_counter()
                loss, committed = trainer.train_step(batch)
                losses.append(float(loss))
                walls.append(time.perf_counter() - t0)
                if not committed:
                    raise AssertionError(
                        f"{name}: step {len(losses)} did not commit")
        mx = trainer.manager.metrics()
        state = trainer.state_dict()
        per_device: Dict[Any, int] = {}
        for leaf in jax.tree_util.tree_leaves(state):
            for shard in leaf.addressable_shards:
                per_device[shard.device] = (per_device.get(shard.device, 0)
                                            + int(shard.data.nbytes))
        return {"losses": losses, "walls": walls, "metrics": mx,
                "state_bytes": n_bytes(state), "per_device": per_device}
    finally:
        if trainer is not None:
            trainer.shutdown()
        lh.shutdown()


def report_steps(name: str, out: Dict[str, Any]) -> None:
    import math

    losses, walls = out["losses"], out["walls"]
    log(f"  {name}: losses " + " ".join(f"{x:.4f}" for x in losses))
    log(f"  {name}: first step (compiles) {walls[0]:.2f} s, then "
        + " ".join(f"{x:.3f}" for x in walls[1:]) + " s")
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{name}: non-finite loss")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{name}: loss did not fall ({losses[0]} -> {losses[-1]})")
    check_digests(name, out["metrics"])


def phase_trainer(run: Run) -> None:
    """One replica group at full width, depth 2, adamw, five steps."""
    sz = run.sizes
    out = train_one_group(run, "solo", sz.depth, (1, sz.seq), sz.steps)
    report_steps("solo", out)
    log(f"  solo: params + optimizer state "
        f"{out['state_bytes'] / 2**30:.2f} GiB; peak_bytes_in_use "
        f"{device_bytes(run.devices[:1])[0]}")


# ------------------------------------------------------------------ phase 3

class Sync:
    """What the two groups of a kill and heal tell each other: named events
    as files in one directory, so that the groups can be threads of this
    process (one chip) or processes of their own (``--chips 4`` (b))."""

    def __init__(self, directory: str) -> None:
        self.dir = directory

    def set(self, name: str) -> None:
        with open(os.path.join(self.dir, name), "w"):
            pass

    def is_set(self, name: str) -> bool:
        return os.path.exists(os.path.join(self.dir, name))

    def wait(self, name: str, timeout: float = 600.0) -> None:
        deadline = time.monotonic() + timeout
        while not self.is_set(name):
            if self.is_set("failed"):
                raise RuntimeError("the other group failed")
            if time.monotonic() > deadline:
                raise TimeoutError(f"waited {timeout:.0f} s for {name!r}")
            time.sleep(0.02)


def leaf_digests(tree: Any) -> List[str]:
    """sha256 of every leaf's bytes, leaf by leaf through the host: equal
    lists are bitwise equal trees, wherever the trees live."""
    import hashlib

    import jax
    import numpy as np

    return [hashlib.sha256(np.ascontiguousarray(np.asarray(x)).tobytes())
            .hexdigest() for x in jax.tree_util.tree_leaves(tree)]


def kill_and_heal_group(tag: str, gi: int, sync: Sync,
                        make_trainer: Callable[[], Any], devices: list,
                        batch: Dict[str, Any], healthy: int = 3,
                        together: int = 2) -> Dict[str, Any]:
    """Group ``gi``'s part of the kill and heal: ``healthy`` steps together;
    group 1 is shut down; group 0 commits alone; group 1 returns as a fresh
    member and heals from group 0; then at least ``together`` more steps.
    ``devices`` are this group's (for the memory readings). Raises unless
    every step committed and the protocol counters are in order; returns
    the group's last step and its parameters' leaf digests."""
    # The solo step; at most one more that slips in before the new
    # trainer's first quorum request (group 0 waits for the trainer, not
    # for its request); the heal step, in which the healer does not count
    # as a participant; then the steps together.
    last_step = healthy + 3 + together
    # Per step: (step, participants, seconds, pack-cache misses).
    lives: Dict[str, List[tuple]] = {}

    def step_until(life: str, trainer: Any, until: int,
                   world: Optional[int] = None) -> None:
        """Step to ``until``. Every step commits: a step that aborts has a
        latched error (out of device memory, a broken ring), which is
        printed and fails the phase. With ``world`` every step must also
        count that many participants."""
        m = trainer.manager
        steps = lives.setdefault(life, [])
        while m.current_step() < until:
            t0 = time.perf_counter()
            _, committed = trainer.train_step(batch)
            s, w = m.current_step(), m.num_participants()
            steps.append((s, w, time.perf_counter() - t0,
                          m.metrics()["allreduce_pack_cache_misses"]))
            used = device_bytes(devices[:1], "bytes_in_use")[0]
            log(f"    {tag} group {life}: step {s}"
                f"{'' if committed else ' (ABORTED)'}, {w} participating, "
                f"{steps[-1][2]:.2f} s"
                + ("" if used is None else f", {used / 2**30:.2f} GiB in use"))
            if not committed:
                raise AssertionError(
                    f"{tag} group {life}: step {s + 1} did not commit: "
                    f"{m.errored()!r}")
            if world is not None and w != world:
                raise AssertionError(
                    f"{tag} group {life}: step {s} counted {w} "
                    f"participants, not {world}")
            if sync.is_set("failed"):
                raise RuntimeError("the other group failed")

    trainer = None
    try:
        trainer = make_trainer()
        sync.set(f"ready {gi}")
        sync.wait(f"ready {1 - gi}")
        step_until(str(gi), trainer, healthy, world=2)
        if gi == 0:
            sync.wait("victim dead")
            step_until("0", trainer, healthy + 1, world=1)
            sync.set("solo done")
            sync.wait("rejoined")
            step_until("0", trainer, last_step)
        else:
            trainer.shutdown()
            trainer = None
            gc.collect()
            sync.set("victim dead")
            sync.wait("solo done")
            trainer = make_trainer()  # fresh id, weights at init
            sync.set("rejoined")
            step_until("1 reborn", trainer, last_step)

        joint = [s for s in lives[max(lives)] if s[0] > healthy + 1
                 and s[1] == 2]
        if len(joint) < together:
            raise AssertionError(
                f"{tag} group {gi}: only {len(joint)} steps together after "
                f"the heal")
        mx = trainer.manager.metrics()
        name = f"{tag} group {gi}"
        n_ar = max(mx["allreduce_count"], 1.0)
        log(f"  {name}: busy ms fetch {mx['allreduce_fetch_ms_total']:.0f}"
            f" ring {mx['allreduce_ring_ms_total']:.0f}"
            f" put {mx['allreduce_put_ms_total']:.0f}"
            f" vote {mx['commit_ms_total']:.0f}"
            f" quorum {mx['quorum_ms_total']:.0f}"
            f" heal {mx['heal_ms_total']:.0f}"
            f" ({mx['heal_bytes_total'] / 2**20:.0f} MiB healed) over "
            f"{mx['allreduce_count']:.0f} exchanges; D2H "
            f"{mx['allreduce_d2h_wire_bytes_total'] / n_ar / 2**20:.0f} "
            f"MiB per exchange")
        if mx["allreduce_d2h_async_fallbacks"] != 0:
            raise AssertionError(
                f"{name}: copy_to_host_async fell back "
                f"{mx['allreduce_d2h_async_fallbacks']:.0f} times")
        check_digests(name, mx)
        if gi == 1 and mx["heal_count"] < 1:
            raise AssertionError(f"{tag}: the restarted group never healed")
        # Pack programs are traced on the first step of a membership (the
        # counter is the process's, so where the groups are threads the
        # other group's trace may land a step later); from the third step
        # of a membership on it must stand still.
        for life, steps in lives.items():
            run_len = 0
            for prev, cur in zip(steps, steps[1:]):
                run_len = run_len + 1 if cur[1] == prev[1] else 0
                if run_len >= 2 and cur[3] != prev[3]:
                    raise AssertionError(
                        f"{tag} group {life}: pack cache missed again at "
                        f"step {cur[0]} of an unchanged membership")
        return {"group": gi, "step": trainer.manager.current_step(),
                "leaves": leaf_digests(trainer.params),
                "peak": device_bytes(devices)}
    except BaseException:
        sync.set("failed")
        raise
    finally:
        if trainer is not None:
            trainer.shutdown()


def assert_groups_equal(tag: str, results: List[Dict[str, Any]]) -> None:
    a, b = sorted(results, key=lambda r: r["group"])
    if a["step"] != b["step"]:
        raise AssertionError(
            f"{tag}: the groups ended at steps {a['step']} and {b['step']}")
    differ = [i for i, (x, y) in enumerate(zip(a["leaves"], b["leaves"]))
              if x != y]
    if differ or len(a["leaves"]) != len(b["leaves"]):
        raise AssertionError(
            f"{tag}: parameter leaves {differ} of {len(a['leaves'])} differ "
            f"between the groups at step {a['step']}")
    log(f"  {tag}: {len(a['leaves'])} parameter leaves bitwise equal across "
        f"the two groups at step {a['step']}")


@contextlib.contextmanager
def tight_hbm_exchange():
    """Two groups of 465 M f32 parameters share one 16 GB chip, each with
    three trees of 1.73 GiB while gradients are exchanged (params, grads,
    averaged grads). The Manager's default stages the packed copy of every
    bucket at once, one more tree per group; its documented bound for
    HBM-tight jobs, one bucket at a time, leaves room. Said in the output,
    since the fetch/ring/put split is read under it."""
    key = "TORCHFT_ALLREDUCE_STAGE_AHEAD"
    prev = os.environ.get(key)
    os.environ[key] = "0"
    log(f"  {key}=0 for this phase: two groups' state leaves no room for "
        f"staging every bucket's packed copy at once")
    try:
        yield
    finally:
        if prev is None:
            del os.environ[key]
        else:
            os.environ[key] = prev


# Groups that are threads make their weights one after the other.
_INIT_LOCK = threading.Lock()


def group_trainer_factory(run: Run, tag: str, gi: int, mesh: Any,
                          lighthouse_addr: str,
                          batch_shape: Tuple[int, int]
                          ) -> Tuple[Callable[[], Any], Dict[str, Any]]:
    """``(make_trainer, batch)`` for group ``gi`` of a kill and heal at
    ``ft_depth`` with sgd. ``mesh`` is the group's fsdp mesh, or None for a
    group that lives on ``run.devices[0]`` whole."""
    import jax
    import optax
    from jax.sharding import NamedSharding

    from torchft_tpu.ops import sharded_flash_attention
    from torchft_tpu.parallel import (FTTrainer, batch_spec,
                                      combined_shardings)

    first = run.devices[0] if mesh is None else mesh.devices.flat[0]
    cfg, model, loss_fn = make_model(
        run, run.sizes.ft_depth,
        flash_fn(run) if mesh is None else
        sharded_flash_attention(mesh, interpret=run.interpret))

    def make_trainer() -> Any:
        # Weights at init are made anew for every trainer and dropped once
        # it has its copy: two groups' state leaves no room to keep them.
        with _INIT_LOCK, jax.default_device(first):
            params = init_params(run, model, first)
            sharded = {} if mesh is None else dict(
                param_shardings=combined_shardings(params, mesh),
                batch_sharding=NamedSharding(
                    mesh, batch_spec(mesh, data_axes=("fsdp",))))
            return FTTrainer(
                loss_fn=loss_fn, tx=optax.sgd(1e-3), params=params,
                manager_factory=make_manager(lighthouse_addr,
                                             f"{tag}_{gi}"), **sharded)

    return make_trainer, make_batch(run, cfg, *batch_shape)


def kill_and_heal_lighthouse() -> Any:
    """min_replicas=1 so that the survivor can commit alone; the join
    timeout only bounds how long a quorum waits for a member that is
    neither there nor gone."""
    from torchft_tpu import Lighthouse

    return Lighthouse(bind="127.0.0.1:0", min_replicas=1,
                      join_timeout_ms=2000, quorum_tick_ms=10)


def phase_fault_tolerance(run: Run) -> None:
    """Two replica groups on the one chip, as threads of this process: the
    fetch/ring/put path, a kill, a solo commit and a heal."""
    import tempfile

    sz = run.sizes
    log(f"  two groups, depth {sz.ft_depth}, sgd, batch 1 x {sz.ft_seq} "
        f"(sequence cut from {sz.seq}: it does not change the gradient "
        f"bytes)")
    results: List[Dict[str, Any]] = []
    errors: List[BaseException] = []

    def group(gi: int, sync: Sync, lh_addr: str) -> None:
        try:
            make_trainer, batch = group_trainer_factory(
                run, "ft", gi, None, lh_addr, (1, sz.ft_seq))
            results.append(kill_and_heal_group(
                "ft", gi, sync, make_trainer, run.devices[:1], batch))
        except BaseException as e:  # noqa: BLE001 — re-raised below
            traceback.print_exc()
            errors.append(e)

    lh = kill_and_heal_lighthouse()
    try:
        with tight_hbm_exchange(), tempfile.TemporaryDirectory() as d:
            threads = [threading.Thread(
                target=group, args=(gi, Sync(d), lh.address()),
                name=f"ft-group-{gi}") for gi in (0, 1)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(1500)
            if errors:
                raise errors[0]
            if any(t.is_alive() for t in threads):
                raise TimeoutError("ft: a replica group hung")
    finally:
        lh.shutdown()
    assert_groups_equal("ft", results)
    log(f"  peak_bytes_in_use (the process's, phase 2 included): "
        f"{device_bytes(run.devices)}")


# ------------------------------------------------------------ --chips 4 (a)

def phase_sharded(run: Run) -> None:
    """One group over four chips (fsdp=2 × tp=2) against the same steps on
    one chip of the four."""
    from torchft_tpu.parallel import make_mesh

    sz = run.sizes
    shape, steps = (2, sz.seq // 2), 3
    one = train_one_group(run, "one-chip", sz.depth, shape, steps)
    report_steps("one-chip", one)
    gc.collect()
    mesh = make_mesh({"fsdp": 2, "tp": 2}, run.devices)
    four = train_one_group(run, "four-chip", sz.depth, shape, steps,
                           mesh=mesh)
    report_steps("four-chip", four)
    for a, b in zip(one["losses"], four["losses"]):
        if abs(a - b) > LOSS_TOL * abs(a):
            raise AssertionError(
                f"four-chip loss {b} differs from one-chip {a} by more "
                f"than {LOSS_TOL} relative")
    log(f"  losses agree within {LOSS_TOL} relative: worst "
        f"{max(abs(a - b) / abs(a) for a, b in zip(one['losses'], four['losses'])):.3e}")
    share = four["state_bytes"] / len(run.devices)
    held = [four["per_device"].get(d, 0) for d in run.devices]
    log("  params + optimizer state per chip (bytes): "
        + " ".join(str(x) for x in held)
        + f"; an even share is {share:.0f}")
    log(f"  peak_bytes_in_use per chip: {device_bytes(run.devices)}")
    if max(held) > SHARE_TOL * share or min(held) == 0:
        raise AssertionError(
            f"state is not spread over the chips: {held} against a share "
            f"of {share:.0f}")


# ------------------------------------------------------------ --chips 4 (b)

def phase_sharded_group(run: Run, gi: int, sync_dir: str,
                        lighthouse_addr: str) -> Dict[str, Any]:
    """One of (b)'s two groups, in a process of its own that sees two of
    the host's four chips: fsdp=2 over them, kill and heal of the sharded
    state against the group in the other process."""
    from torchft_tpu.parallel import make_mesh

    sz = run.sizes
    mesh = make_mesh({"fsdp": 2}, run.devices)
    shape = (2, sz.seq // 2)
    log(f"  group {gi}: two chips (fsdp=2), depth {sz.ft_depth}, sgd, "
        f"batch {shape[0]} x {shape[1]}")
    make_trainer, batch = group_trainer_factory(
        run, "hsdp", gi, mesh, lighthouse_addr, shape)
    return kill_and_heal_group("hsdp", gi, Sync(sync_dir), make_trainer,
                               run.devices, batch)


def free_port() -> int:
    import socket

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def two_chip_env(gi: int) -> Dict[str, str]:
    """The environment that gives a process chips ``2*gi, 2*gi+1`` of a
    v5e 2x2 host as its whole topology (the spelling of jax's own
    multi-process TPU tests)."""
    port = free_port()
    return {"TPU_VISIBLE_CHIPS": f"{2 * gi},{2 * gi + 1}",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,2,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
            "TPU_PROCESS_PORT": str(port),
            "CLOUD_TPU_TASK_ID": "0",
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}


class Part:
    """One child process of ``--chips 4``: this script again with
    ``--part``. Its standard output is passed on line by line; its
    ``RESULT`` line is kept."""

    def __init__(self, args: argparse.Namespace, part: str,
                 extra: List[str], env: Dict[str, str]) -> None:
        import subprocess

        cmd = [sys.executable, os.path.abspath(__file__), "--chips", "4",
               "--seed", str(args.seed), "--part", part, *extra]
        cmd += [f for f in ("--rehearse", "--verbose")
                if getattr(args, f[2:])]
        self.part = part
        self.result: Optional[Dict[str, Any]] = None
        self.proc = subprocess.Popen(
            cmd, env={**os.environ, **env}, stdout=subprocess.PIPE,
            text=True)
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()

    def _read(self) -> None:
        assert self.proc.stdout is not None
        for line in self.proc.stdout:
            if line.startswith("RESULT "):
                self.result = json.loads(line[len("RESULT "):])
            else:
                log(line.rstrip("\n"))

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self.reader.join(10)


def run_parts(parts: List[Part], limit: float) -> List[Dict[str, Any]]:
    """Wait for every part; the first that fails, or the time limit, stops
    the rest (a group cannot finish without its peer)."""
    deadline = time.monotonic() + limit
    try:
        while any(p.proc.poll() is None for p in parts):
            bad = [p for p in parts if p.proc.poll() not in (None, 0)]
            if bad or time.monotonic() > deadline:
                why = (f"part {bad[0].part} exited with "
                       f"{bad[0].proc.returncode}" if bad else
                       f"no end after {limit:.0f} s")
                time.sleep(5 if bad else 0)  # let the peer say why, too
                raise RuntimeError(why)
            time.sleep(0.1)
    finally:
        for p in parts:
            p.stop()
    for p in parts:
        if p.proc.returncode != 0 or not (p.result or {}).get("ok"):
            raise RuntimeError(f"part {p.part} failed")
    return [p.result for p in parts]


def orchestrate_four(args: argparse.Namespace) -> int:
    """``--chips 4``: this process starts the parts and never initialises
    a JAX backend, so that each chip belongs to one process at a time —
    (a) in one process with all four; then (b) with one process per
    replica group, two chips each, which is how a pod runs them
    (docs/pod_runbook.md). Two groups of two chips as threads of ONE
    process halt or hang on libtpu 0.0.34: scripts/tpu_submesh_repro.py,
    PERF.md (Findings, PR 25)."""
    import tempfile

    ok, device = False, {"platform": None, "kind": None, "count": 0}
    cache = {"hits": 0, "misses": 0}
    t_run = time.perf_counter()
    failed = "a sharded group"
    try:
        log(f"[phase {failed}] one process, all four chips")
        (res_a,) = run_parts([Part(args, "a", [], {})], limit=900)
        device = res_a["device"]
        failed = "b sharded kill and heal"
        log(f"[phase {failed}] one process per replica group, two chips "
            f"each; this process holds the lighthouse")
        t0 = time.perf_counter()
        lh = kill_and_heal_lighthouse()
        try:
            with tempfile.TemporaryDirectory() as d:
                extra = ["--sync-dir", d, "--lighthouse", lh.address()]
                results = run_parts(
                    [Part(args, f"b{gi}", extra,
                          {} if args.rehearse else two_chip_env(gi))
                     for gi in (0, 1)], limit=900)
        finally:
            lh.shutdown()
        assert_groups_equal("hsdp", [r["phase"] for r in results])
        log("  peak_bytes_in_use per chip: "
            f"{[b for r in results for b in r['phase']['peak']]}")
        log(f"[phase {failed}] passed in {time.perf_counter() - t0:.1f} s "
            f"wall")
        for r in (res_a, *results):
            for k in cache:
                cache[k] += r["cache"][k]
        ok = True
    except BaseException:  # noqa: BLE001 — reported, then exit non-zero
        traceback.print_exc()
        log(f"FAILED in phase {failed}")
    log(f"compiled programs: {cache['misses']} compiled and written to the "
        f"cache, {cache['hits']} read from it")
    log(f"total {time.perf_counter() - t_run:.1f} s")
    sys.stderr.flush()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


# --------------------------------------------------------------------- main

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases 0-3 on one chip (default). 4: only the "
                         "sharded path and what it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the batch")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny widths, interpreted kernels: checks "
                         "paths and control flow, never reports a TPU")
    ap.add_argument("--verbose", action="store_true",
                    help="also send the library's INFO log to standard "
                         "error (quorum, heal and commit lines)")
    # How --chips 4 starts its parts; not for users.
    ap.add_argument("--part", choices=("a", "b0", "b1"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--sync-dir", help=argparse.SUPPRESS)
    ap.add_argument("--lighthouse", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.part is not None and args.chips != 4:
        ap.error("--part belongs to --chips 4")
    if args.verbose:
        import logging

        logging.basicConfig(
            level=logging.INFO, format=f"%(asctime)s {args.part or 'main'} "
            f"%(threadName)s %(name)s %(message)s")
    if args.chips == 4 and args.part is None:
        return orchestrate_four(args)
    want = {None: 1, "a": 4, "b0": 2, "b1": 2}[args.part]

    if args.rehearse:
        from torchft_tpu.utils import force_cpu_devices

        force_cpu_devices(want)

    import jax
    import jax.monitoring

    cache = {"hits": 0, "misses": 0}
    compile_secs = [0.0]

    def on_event(event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    def on_duration(event: str, secs: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_secs[0] += secs

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    ok, device = False, {"platform": None, "kind": None, "count": 0}
    failed: Optional[str] = None
    out: Optional[Dict[str, Any]] = None
    t_run = time.perf_counter()
    try:
        failed = "0 device"
        log(f"[phase {failed}]")
        devices = phase_device(args, want)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        run = Run(args, devices)
        if args.rehearse:
            log("REHEARSAL: tiny widths on the CPU, kernels interpreted; "
                "nothing below is a result")
        else:
            log("widths: Llama-2-7B as published (embed 4096, 32 heads x "
                "128, FFN 11008, vocab 32000, 4096 tokens); the only cut "
                "is depth")
        if args.part is None:
            phases = [("1 kernel", phase_kernel),
                      ("2 trainer", phase_trainer),
                      ("3 fault tolerance", phase_fault_tolerance)]
        elif args.part == "a":
            phases = [("a sharded group", phase_sharded)]
        else:
            gi = int(args.part[1])
            phases = [(f"b group {gi}", lambda run: phase_sharded_group(
                run, gi, args.sync_dir, args.lighthouse))]
        for failed, phase in phases:
            log(f"[phase {failed}] bytes in use at its start: "
                f"{device_bytes(devices, 'bytes_in_use')}")
            t0, c0 = time.perf_counter(), compile_secs[0]
            out = phase(run)
            gc.collect()
            log(f"[phase {failed}] passed in "
                f"{time.perf_counter() - t0:.1f} s wall, of which "
                f"{compile_secs[0] - c0:.1f} s compiling or reading "
                f"compiled programs")
        failed = None
        ok = True
    except BaseException:  # noqa: BLE001 — reported, then exit non-zero
        traceback.print_exc()
        log(f"FAILED in phase {failed}")
        if args.sync_dir:
            Sync(args.sync_dir).set("failed")
    if args.part is not None:
        # A part reports to the process that started it.
        sys.stderr.flush()
        print("RESULT " + json.dumps({"ok": ok, "device": device,
                                      "cache": cache, "phase": out}),
              flush=True)
        return 0 if ok else 1
    log(f"compiled programs: {cache['misses']} compiled and written to the "
        f"cache, {cache['hits']} read from it")
    log(f"total {time.perf_counter() - t_run:.1f} s")
    sys.stderr.flush()
    # A rehearsal proves paths, not the chip: it never says ok for a TPU
    # (its device is the CPU it ran on).
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
