"""Ahead-of-time compiles for the chip that is not attached here.

The TPU compiler is installed in this sandbox and compiles for a *described*
v5e 2x2 host (section 2 of the on-chip-measurement guide): what it refuses
here, the chip would refuse too — a kernel that cannot be partitioned, a
program that does not fit 16 GB — and it costs no chip time. These are the
programs ``chip_smoke.py`` runs at its real sizes: Llama-2-7B widths, depth
cut to 2 (adamw, one group) or 1 (sgd, two groups).

This is the only file that describes a TPU topology, and it does so inside a
module-scoped fixture: only one process may hold the TPU library, so the call
must never run while a module is imported or collected. A compile that passes
is not a chip run.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from torchft_tpu.models import (Transformer, chunked_causal_lm_loss,
                                llama2_7b_config, tp_rules)
from torchft_tpu.ops import flash_attention, sharded_flash_attention
from torchft_tpu.parallel import batch_spec, combined_shardings

GiB = 2 ** 30
# What a v5e chip can hand out: 16 GB of HBM less the runtime's reserve
# (the compiler's own refusal quotes 15.75G).
HBM_BYTES = int(15.75 * GiB)
SEQ = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip is written to the persistent
    # cache but cannot be read back without the chip: keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shaped(tree, sharding):
    """Shapes with a sharding (one for all leaves, or a matching tree)."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree_util.tree_map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            tree, sharding)
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _model(depth: int, attention_fn):
    model = Transformer(llama2_7b_config(
        num_layers=depth, attention_fn=attention_fn, remat=True))

    def loss_fn(params, batch):
        hidden = model.apply(params, batch["tokens"], return_hidden=True)
        return chunked_causal_lm_loss(
            hidden, params["params"]["lm_head"]["kernel"], batch["tokens"])

    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0))
    return loss_fn, shapes


def _compiled_flash():
    return functools.partial(flash_attention, interpret=False)


def _trainer_programs(loss_fn, tx):
    """The three programs FTTrainer / FTOptimizer jit (parallel/step.py,
    optim.py), spelled the same way."""
    def fwd_bwd(p, batch):
        return jax.value_and_grad(loss_fn)(p, batch)

    def fused(p, o, batch):  # single-group step: NOT donated
        loss, grads = fwd_bwd(p, batch)
        updates, new_o = tx.update(grads, o, p)
        return loss, optax.apply_updates(p, updates), new_o

    def update(p, o, grads):
        updates, new_o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), new_o

    return (jax.jit(fwd_bwd), jax.jit(fused),
            jax.jit(update, donate_argnums=(0, 1)))


@pytest.mark.parametrize("fused_bwd", ["1", "0"], ids=["fused", "split"])
def test_flash_fwd_bwd_compiles_as_a_kernel(one_chip, monkeypatch,
                                            fused_bwd):
    """[1, 4096, 32, 128] bf16 causal (Llama-2-7B heads), both backward
    spellings: a Mosaic custom call, not interpreted XLA ops."""
    monkeypatch.setenv("TORCHFT_FLASH_FUSED_BWD", fused_bwd)
    x = jax.ShapeDtypeStruct((1, SEQ, 32, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    # forward + one fused backward kernel, or forward + dq + dk/dv
    assert text.count("tpu_custom_call") >= (2 if fused_bwd == "1" else 3)


@pytest.mark.parametrize("fused_bwd", ["1", "0"], ids=["fused", "split"])
def test_flash_compiles_at_a_head_of_64_on_eight_kv_heads(one_chip,
                                                         monkeypatch,
                                                         fused_bwd):
    """[1, 8192, 32, 64] queries on [1, 8192, 8, 64] keys and values, bf16
    causal (``lfm2-8b-a1b``'s attention layer at its cell's length; every
    other configuration runs heads of 128 to 256): forward, fused backward
    and split backward lower as Mosaic kernels with the tiles of a head of
    128, half a lane tile wide, and stay inside scoped VMEM."""
    monkeypatch.setenv("TORCHFT_FLASH_FUSED_BWD", fused_bwd)
    q = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    c = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    text = c.as_text()
    assert text.count("tpu_custom_call") >= (2 if fused_bwd == "1" else 3)
    assert "bf16[32,8192,64]" in text and "bf16[8,8192,64]" in text


@pytest.mark.parametrize("fused_bwd", ["1", "0"], ids=["fused", "split"])
def test_latent_flash_compiles_at_two_head_sizes(one_chip, monkeypatch,
                                                 fused_bwd):
    """[1, 8192, 32, 192] queries and keys against [1, 8192, 32, 128]
    values, bf16 causal (the latent attention of ``joyai-llm-flash`` at its
    cell's length): every kernel lowers with the narrower value blocks, at
    1024-token tiles inside the two lane tiles' scoped VMEM it asks for
    (PR 57), and carries its own ``_mla`` name."""
    monkeypatch.setenv("TORCHFT_FLASH_FUSED_BWD", fused_bwd)
    qk = jax.ShapeDtypeStruct((1, 8192, 32, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile().as_text()
    want = (("flash_fwd_mla", "flash_bwd_mla") if fused_bwd == "1" else
            ("flash_fwd_mla", "flash_bwd_dq_mla", "flash_bwd_dkdv_mla"))
    for name in want:
        assert name in text
    assert "bf16[32,8192,128]" in text and "bf16[32,8192,192]" in text


@pytest.mark.parametrize("fused_bwd", ["1", "0"], ids=["fused", "split"])
def test_flash_compiles_at_head_256_on_2_kv_heads(one_chip, monkeypatch,
                                                  fused_bwd):
    """[1, 8192, 16, 256] queries on [1, 8192, 2, 256] keys and values, bf16
    causal (the full-attention layers of ``qwen3-next-80b-a3b`` at its
    cell's length): 1024-token tiles inside the two lane tiles' scoped
    VMEM it asks for (512-token tiles inside the default before PR 57), the
    key/value heads shared through the index maps and not repeated in
    memory."""
    monkeypatch.setenv("TORCHFT_FLASH_FUSED_BWD", fused_bwd)
    q = jax.ShapeDtypeStruct((1, 8192, 16, 256), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 2, 256), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") >= (2 if fused_bwd == "1" else 3)
    assert "bf16[16,8192,256]" in text and "bf16[2,8192,256]" in text


# One chip's VMEM (v5e: 128 MiB), which a kernel's ``vmem_limit_bytes``
# has to stay under.
VMEM_BYTES = 128 << 20
#   batch, tokens, q heads, kv heads, d_qk, d_v, window: the cells' shapes
CELL_SHAPES = {
    "trinity_full": (1, 8192, 32, 4, 128, 128, None),
    "trinity_window": (1, 8192, 32, 4, 128, 128, 2048),
    "joyai_latent": (1, 8192, 32, 32, 192, 128, None),
    "qwen3_next_head_256": (1, 8192, 16, 2, 256, 256, None),
    "lfm2_head_64": (1, 8192, 32, 8, 64, 64, None),
    "mistral_4x4096": (4, 4096, 32, 8, 128, 128, None),
}


@pytest.mark.parametrize("cell", list(CELL_SHAPES), ids=list(CELL_SHAPES))
def test_fused_backward_keeps_dq_in_vmem_at_the_cells_shapes(one_chip,
                                                             monkeypatch,
                                                             cell):
    """The forward (K and V clamped to the visible band) and the fused
    backward (one (batch, head)'s dq held in VMEM for its whole sweep) at
    every shape a cell runs: two Mosaic kernels, the backward asking for
    the scoped VMEM of its head's lane tiles (the default a tile) plus the
    resident dq and no more, far under the chip's 128 MiB."""
    import importlib
    import re

    fa = importlib.import_module("torchft_tpu.ops.flash_attention")
    monkeypatch.delenv("TORCHFT_FLASH_FUSED_BWD", raising=False)
    b, s, h, h_kv, d, d_v, window = CELL_SHAPES[cell]
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, s, h_kv, d), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, s, h_kv, d_v), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               window=window).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile().as_text()
    assert text.count("tpu_custom_call") == 2   # forward, fused backward
    asked = [int(n) for n in re.findall(
        r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', text)]
    want = fa._fused_vmem_limit(s, d, 2)
    tiles = fa._SCOPED_VMEM_BYTES * fa._lane_tiles(d)
    assert want == tiles + fa._dq_resident_bytes(s, d, 2)
    assert max(asked) == want < VMEM_BYTES // 2
    # XLA's own fusions keep to the default, the forward to its tiles'
    assert set(asked) <= {fa._SCOPED_VMEM_BYTES, tiles, want}


def _compiled_delta_rule(monkeypatch):
    """``gated_delta_rule`` decides by the backend whether its kernels run
    interpreted, and the backend here is the CPU: steered in the test."""
    import importlib

    gd = importlib.import_module("torchft_tpu.ops.gated_delta")
    monkeypatch.setattr(gd, "_resolve_interpret", lambda _: False)
    return gd


@pytest.mark.parametrize("tokens", [8192, 8192 + 96], ids=["8k", "ragged"])
def test_gated_delta_rule_compiles_at_the_published_sizes(one_chip, tokens,
                                                          monkeypatch):
    """The rule forward and backward at 16 key heads, 32 value heads of 128
    (``ops/gated_delta.py``): three Mosaic kernels, the chunk inverse
    (``gdn_inv``: substitution, so no triangular solve) and the chunk
    recurrence (``gdn_fwd`` keeping the states, ``gdn_bwd``: no loop
    carries the state ``f32[1,32,128,128]``), and the recurrence's kernels
    ask for the VMEM they are written to and are given it."""
    import re

    gd = _compiled_delta_rule(monkeypatch)

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (shaped(1, tokens, 16, 128), shaped(1, tokens, 16, 128),
            shaped(1, tokens, 32, 128),
            shaped(1, tokens, 32, dtype=jnp.float32),
            shaped(1, tokens, 32, dtype=jnp.float32))
    c = jax.jit(jax.grad(lambda *a: gd.gated_delta_rule(*a).sum(),
                         argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    text = c.as_text()
    kernels = {name: line for line in text.splitlines()
               if "tpu_custom_call" in line
               for name in re.findall(r"gdn_(?:inv|fwd|bwd)", line)[:1]}
    assert sorted(kernels) == ["gdn_bwd", "gdn_fwd", "gdn_inv"]
    assert text.count("tpu_custom_call") == 3
    assert "while(" not in text and "f32[1,32,128,128]" not in text
    assert "riangular" not in text
    chunks = -(-tokens // 64)
    assert f"f32[32,{chunks},128,128]" in kernels["gdn_fwd"]   # the states
    lanes = -(-32 * chunks // 128) * 128        # whole vectors of matrices
    assert f"f32[64,64,{lanes}]" in kernels["gdn_inv"]
    assert gd._heads_a_step(32, 128, 128, 2) == 16
    for name in ("gdn_fwd", "gdn_bwd"):
        asked = re.findall(
            r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
            kernels[name])
        assert asked == [str(gd._VMEM_LIMIT_BYTES)]
    assert gd._TILE_BYTES < gd._VMEM_LIMIT_BYTES < VMEM_BYTES // 2
    assert _footprint(c) < 3 * 2**30


def _compiled_state_space_scan(monkeypatch):
    """``ssd_scan`` decides as the delta rule does: steered the same way."""
    import importlib

    ssd = importlib.import_module("torchft_tpu.ops.ssd")
    monkeypatch.setattr(ssd, "_resolve_interpret", lambda _: False)
    return ssd


@pytest.mark.parametrize("tokens", [8192, 8192 + 96], ids=["8k", "ragged"])
def test_state_space_scan_compiles_at_the_published_sizes(one_chip, tokens,
                                                          monkeypatch):
    """The Mamba-2 scan forward and backward at 64 heads of 64 in 8 groups,
    state 128 (``ops/ssd.py``): two Mosaic kernels, ``ssd_fwd`` handing
    ``ssd_bwd`` the state each chunk found, float32 [1, chunks, 64 x 64, 128],
    a group's 8 heads a grid step; no loop, and nowhere in the program the
    batched products' ``[8, 8, chunks, 128, 128]`` triangles or their
    per-chunk states ``[8, 8, chunks, 64, 128]``; the kernels ask for the
    VMEM they are written to and are given it."""
    import re

    ssd = _compiled_state_space_scan(monkeypatch)

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    args = (shaped(1, tokens, 64, 64), shaped(1, tokens, 64, dtype=f32),
            shaped(64, dtype=f32), shaped(1, tokens, 8, 128),
            shaped(1, tokens, 8, 128), shaped(64, dtype=f32))
    c = jax.jit(jax.grad(lambda *a: ssd.ssd_scan(*a).sum(),
                         argnums=tuple(range(6)))).lower(*args).compile()
    text = c.as_text()
    kernels = {name: line for line in text.splitlines()
               if "tpu_custom_call" in line
               for name in re.findall(r"ssd_(?:fwd|bwd)", line)[:1]}
    assert sorted(kernels) == ["ssd_bwd", "ssd_fwd"]
    assert text.count("tpu_custom_call") == 2
    chunks = -(-tokens // 128)
    states = f"f32[1,{chunks},4096,128]"
    assert f"{states}{{3,2,1,0" in kernels["ssd_fwd"].split(
        "custom-call(")[0]                                    # written
    assert states in kernels["ssd_bwd"].split("custom-call(")[1]   # read
    assert "while(" not in text
    assert f"8,8,{chunks},128,128]" not in text
    assert f"8,8,{chunks},64,128]" not in text
    assert ssd._heads_a_step(8, 64, 128, 2) == 8
    for line in kernels.values():
        asked = re.findall(
            r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', line)
        assert asked == [str(ssd._VMEM_LIMIT_BYTES)]
    assert ssd._TILE_BYTES < ssd._VMEM_LIMIT_BYTES < VMEM_BYTES // 2
    assert _footprint(c) < 2**30


def test_one_group_step_depth2_fits_the_chip(one_chip):
    """Phase 2: the single-group fused step holds the old and the new
    params + adam state at once (it is not donated), which at depth 2 is
    within a quarter GiB of the chip; the donated update is far below."""
    loss_fn, pshape = _model(2, _compiled_flash())
    tx = optax.adamw(3e-4)
    p = _shaped(pshape, one_chip)
    o = _shaped(jax.eval_shape(tx.init, pshape), one_chip)
    batch = {"tokens": jax.ShapeDtypeStruct((1, SEQ), jnp.int32,
                                            sharding=one_chip)}
    _, fused, update = _trainer_programs(loss_fn, tx)
    c = fused.lower(p, o, batch).compile()
    assert "tpu_custom_call" in c.as_text()
    assert 14 * GiB < _footprint(c) < HBM_BYTES
    assert _footprint(update.lower(p, o, p).compile()) < 11 * GiB


def test_two_group_programs_depth1_fit_twice(one_chip):
    """Phase 3: two groups share the chip, so fwd_bwd (params in, grads
    out) plus the averaged grads coming back must fit twice over."""
    loss_fn, pshape = _model(1, _compiled_flash())
    tx = optax.sgd(1e-3)
    p = _shaped(pshape, one_chip)
    o = _shaped(jax.eval_shape(tx.init, pshape), one_chip)
    batch = {"tokens": jax.ShapeDtypeStruct((1, SEQ), jnp.int32,
                                            sharding=one_chip)}
    fwd_bwd, _, update = _trainer_programs(loss_fn, tx)
    grads_bytes = sum(int(np.prod(l.shape)) * 4
                      for l in jax.tree_util.tree_leaves(pshape))
    step = _footprint(fwd_bwd.lower(p, batch).compile()) + grads_bytes
    assert 2 * step < HBM_BYTES
    assert _footprint(update.lower(p, o, p).compile()) < 2 * grads_bytes + GiB


def test_boundary_programs_over_the_depth2_state(one_chip):
    """The attestation digest over params + adam state (7.45 GiB) and the
    int8 quantize-pack of the largest leaf (the 500 MB embedding): their
    ravel/concatenate must not need a second copy of what they read."""
    from torchft_tpu import exchange as exchange_mod
    from torchft_tpu import manager as manager_mod

    _, pshape = _model(2, None)
    state = {"params": pshape,
             "opt_state": jax.eval_shape(optax.adamw(3e-4).init, pshape)}
    leaves = jax.tree_util.tree_leaves(_shaped(state, one_chip))
    state_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                      for l in leaves)
    assert state_bytes > 7 * GiB

    # The jitted functions live behind wrappers that execute; reach them
    # by running each once on a few elements.
    tiny = [jnp.zeros((8,), jnp.float32)]
    manager_mod._attest_device_words(tiny)
    attest = manager_mod._ATTEST_FNS["attest"]
    m = attest.lower(leaves).compile().memory_analysis()
    assert m.temp_size_in_bytes < 64 * 2 ** 20
    assert m.output_size_in_bytes <= 1024

    exchange_mod._device_quantize_pack(tiny, jnp.zeros((8,), jnp.float32))
    (quant,) = [f for f in exchange_mod._DEV_QUANT_FNS.values()]
    big = max(jax.tree_util.tree_leaves(_shaped(pshape, one_chip)),
              key=lambda l: int(np.prod(l.shape)))
    n = int(np.prod(big.shape))
    assert n * 4 >= 500 * 2 ** 20
    res = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    c = quant.lower([big], res).compile()
    # leaf + residual in, int8 payload + new residual out, and temporaries
    # of about three more leaves (6.25x the leaf in all, 3.05 GiB): it
    # fits beside two groups' depth-1 params and grads, not much more.
    assert _footprint(c) < 7 * n * 4


@pytest.mark.parametrize("shape", [(32000, 4096), (4096, 14336),
                                   (92544, 2048)],
                         ids=["embedding", "mlp", "internlm2_vocab"])
def test_slice_programs_copy_no_leaf(one_chip, shape):
    """The exchange's slice of a wide leaf (``_SLICE_BYTES``): the pack
    cuts rows of the leaf and the put writes them into the donated
    leaf-shaped buffer. Neither may need a temporary of the leaf's size
    (a ravel of the whole leaf before the cut does: 500 MiB for the
    embedding), or two groups' exchange no longer fits beside their
    state."""
    from torchft_tpu import exchange as exchange_mod

    sched = exchange_mod._derive_schedule(((shape, "float32"),), 4 << 20,
                                         None)
    leaf_bytes = int(np.prod(shape)) * 4
    assert sched.slices[0] == -(-leaf_bytes // (
        exchange_mod._SLICE_BYTES // (shape[1] * 4) * shape[1] * 4))
    full, tail = sched.chunks[0][0], sched.chunks[-1][0]
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    for c in (full, tail):
        lead, _, count = c.rows
        assert c.total * 4 <= exchange_mod._SLICE_BYTES
        pack = exchange_mod._pack_fn("float32", lead, count)
        m = pack.lower(leaf, scalar).compile().memory_analysis()
        assert m.temp_size_in_bytes < 2 ** 20
        assert m.output_size_in_bytes == c.total * 4
        upd = jax.ShapeDtypeStruct((c.total,), jnp.float32,
                                   sharding=one_chip)
        put = exchange_mod._put_slice(c)
        m = put.lower(leaf, upd, scalar, scalar).compile().memory_analysis()
        assert m.alias_size_in_bytes == leaf_bytes  # assembled in place
        assert m.temp_size_in_bytes < 2 ** 20


def test_sharded_group_step_compiles_for_four_chips(topo):
    """--chips 4 (a): the fused step over a fsdp=2 x tp=2 mesh of the
    described chips, flash attention through the library's shard_map
    wrapper. Each chip holds about a quarter of params + adam state."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("fsdp", "tp"))
    loss_fn, pshape = _model(
        2, sharded_flash_attention(mesh, interpret=False))
    tx = optax.adamw(3e-4)
    oshape = jax.eval_shape(tx.init, pshape)
    p = _shaped(pshape, combined_shardings(pshape, mesh, tp_rules()))
    o = _shaped(oshape, combined_shardings(oshape, mesh, tp_rules()))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (2, SEQ // 2), jnp.int32,
        sharding=NamedSharding(mesh, batch_spec(mesh,
                                                data_axes=("fsdp",))))}
    _, fused, _ = _trainer_programs(loss_fn, tx)
    c = fused.lower(p, o, batch).compile()
    assert "tpu_custom_call" in c.as_text()
    state_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                      for l in jax.tree_util.tree_leaves((pshape, oshape)))
    per_chip_args = c.memory_analysis().argument_size_in_bytes
    assert per_chip_args < 1.3 * state_bytes / 4
    assert _footprint(c) < HBM_BYTES


def test_bare_kernel_in_a_sharded_jit_is_refused(topo):
    """Mosaic kernels cannot be partitioned automatically. The bare kernel
    under a jit sharded over several chips is refused by jax itself, which
    says to wrap it in a shard_map: ``sharded_flash_attention`` (above) is
    that wrapping, and ``flash_attention``'s docstring says so."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("fsdp", "tp"))
    x = jax.ShapeDtypeStruct(
        (2, SEQ, 32, 128), jnp.bfloat16,
        sharding=NamedSharding(mesh, jax.sharding.PartitionSpec(
            "fsdp", None, "tp", None)))

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        jax.jit(jax.grad(loss)).lower(x, x, x).compile()


CELL_STEPS = {
    "trinity-mini.steady-1g-8k": ("flash_fwd_window", "gmm"),
    "joyai-llm-flash.steady-1g-8k": ("flash_fwd_mla", "flash_bwd_mla",
                                     "gmm"),
    "qwen3-next-80b-a3b.steady-1g-8k": ("%attn", "gmm", "%gdn_fwd",
                                        "%gdn_bwd", "%gdn_inv",
                                        "f32[32,128,128,128]",
                                        "bf16[16,8192,256]"),
    "nemotron-3-nano-30b-a3b.steady-1g-8k": ("%attn", "gmm", "%ssd_fwd",
                                             "%ssd_bwd",
                                             "f32[1,64,4096,128]",
                                             "bf16[32,8192,128]"),
    "lfm2-8b-a1b.steady-1g-8k": ("%attn", "gmm", "bf16[32,8192,64]",
                                 "bf16[8,8192,64]", "bf16[1,8192,6144]",
                                 "bf16[8,2048,1792]"),
    "smallthinker-21b-a3b.steady-1g-8k": ("flash_fwd_window", "%attn", "gmm",
                                          "bf16[28,8192,128]",
                                          "bf16[4,8192,128]",
                                          "bf16[16,2560,768]"),
}
# What has to fit beside the step. The first three cells were sized when the
# driver's oracle kept one more seeded tree there (until PR 44), and keep
# that room; the fourth was sized after, for the oracle's one thinned sample
# (0.3 GiB of a tree of 1.97, read on the chip in PR 45), and so was the
# fifth (PR 47) and the sixth (PR 51: 16 of 64 experts held, the case
# ISSUE 51's fallback rule reads; a tree of 2.08 GiB).
SAMPLE_ROOM = {"nemotron-3-nano-30b-a3b.steady-1g-8k": int(0.3 * GiB),
               "lfm2-8b-a1b.steady-1g-8k": int(0.3 * GiB),
               "smallthinker-21b-a3b.steady-1g-8k": int(0.4 * GiB)}


def _cell_fused_step(name, one_chip, **model_kw):
    """A one-group cell's fused step as ``benchmarks/`` builds it (not
    donated, adamw), compiled for the described chip: ``(compiled,
    builder, configuration)``."""
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import spec

    cell = spec.Cell(name)
    cfg, seq, batch = (cell.config, int(cell.mix["seq"]),
                       int(cell.mix["batch_per_group"]))
    builder = spec.model_of(cfg)
    loss_fn = builder.make_loss_fn(cfg, seq, interpret=False, **model_kw)
    pshape = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        builder.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    tx = optax.adamw(3e-4)
    p = _shaped(pshape, one_chip)
    o = _shaped(jax.eval_shape(tx.init, pshape), one_chip)
    tokens = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                             sharding=one_chip)}
    _, fused, _ = _trainer_programs(loss_fn, tx)
    return fused.lower(p, o, tokens).compile(), builder, cfg


@pytest.mark.parametrize("name", list(CELL_STEPS), ids=list(CELL_STEPS))
def test_sparse_cells_step_fits_the_chip(one_chip, name, monkeypatch):
    """A sparse configuration's 8k cell as ``benchmarks/`` builds it: the
    fused one-group step (not donated) at the published widths, the cut's
    layers and held experts and the cell's own batch of 8192-token
    sequences, adamw. ``trinity-mini``: windowed and full flash kernels;
    ``joyai-llm-flash``: the 192/128 latent kernels (forward and the fused
    backward) in four layers and the prediction module, two loss scans over
    one head; ``qwen3-next-80b-a3b``: three Gated DeltaNet layers (the
    scans that carry ``f32[1,32,128,128]``) and one full layer's flash
    kernel at 16 heads of 256, 16 of 512 experts held;
    ``nemotron-3-nano-30b-a3b``: three Mamba-2 blocks (the scan's kernels
    ``ssd_fwd`` and ``ssd_bwd`` and between them the states the chunks found,
    ``f32[1,64,4096,128]``; no ``[8,8,64,128,128]`` triangle), three relu^2
    expert blocks whose grouped products tile 2688 and 1856 by 896 and 640, one attention block
    at 32 heads of 128; ``lfm2-8b-a1b``: four gated short convolutions (the
    three streams ``[1,8192,6144]``), the flash kernels at 32 heads of 64
    on 8 key/value heads, four expert layers holding 8 of 32 at a width of
    1792, a head that is the table (49 leaves a tree, no ``lm_head``);
    ``smallthinker-21b-a3b``: the windowed and full flash kernels at 28 query
    heads of 128 on 4 key/value heads (a group of 7), four ReGLU expert
    layers holding 16 of 64 at a width of 768, routed on the attention's
    input. The grouped matmuls compile as Mosaic custom calls, and the step fits with
    the room the driver's oracle needs beside it."""
    _compiled_delta_rule(monkeypatch)
    _compiled_state_space_scan(monkeypatch)
    c, builder, cfg = _cell_fused_step(name, one_chip)
    text = c.as_text()
    for kernel in CELL_STEPS[name]:
        assert kernel in text
    assert "f32[1,32,128,128]" not in text and "riangular" not in text
    assert "8,8,64,128,128]" not in text
    tree = 4 * builder.param_count(cfg)
    assert 6 * tree < _footprint(c) < HBM_BYTES - SAMPLE_ROOM.get(name, tree)


def test_looped_cell_step_is_one_pass_and_fits_the_chip(one_chip):
    """``ouro-2.6b.steady-1g-8k`` as ``benchmarks/`` builds it: the fused
    one-group step at the published widths, layers 0-5, FOUR passes over
    them and one sequence of 8,192 tokens. One scan, one body: the flash
    kernels number one pass's (a forward, the rematerialised forward and
    the fused backward a layer, 18; the same depth run once has 12: there
    the scan of one pass is no loop, and outside a loop XLA merges the
    rematerialised forward with the first, PERF.md section 7). The
    serialized executable reads 1.47 times that of the same depth run once
    (60.9 MB against 41.5; ISSUE 56 asked for 1.3): what four passes add
    is the third kernel a layer, the loop's carries and an adam that no
    longer fuses into the layers' backward, nothing that grows with the
    passes (two passes compile to MORE, 76.3 MB: XLA peels a loop of two),
    where four passes written out would be four bodies. One loss scan
    carries the float32 ``[2048,49152]`` head gradient, not one an exit;
    the step's temporaries stay under 3.6 GiB (read 2.28: the looped leaves'
    carried float32 gradients 1.15 and 24 saved layer inputs 0.75 among
    them) and the whole under 15.0 GiB (read 13.68): the number ISSUE 56's
    fallback rule (five layers) reads."""
    from jax.experimental import serialize_executable

    looped, builder, cfg = _cell_fused_step("ouro-2.6b.steady-1g-8k",
                                            one_chip)
    once, _, _ = _cell_fused_step("ouro-2.6b.steady-1g-8k", one_chip,
                                  loop_steps=1)
    text = looped.as_text()
    assert "%attn" in text
    layers = int(cfg["num_hidden_layers"])
    calls = (text.count("tpu_custom_call"),
             once.as_text().count("tpu_custom_call"))
    assert calls == (3 * layers, 2 * layers), calls
    carries = [line for line in text.splitlines()
               if " while(" in line and "f32[2048,49152]" in line]
    assert len(carries) == 1
    sizes = [len(serialize_executable.serialize(c)[0])
             for c in (looped, once)]
    assert sizes[0] <= 1.6 * sizes[1], sizes
    temporaries = looped.memory_analysis().temp_size_in_bytes
    assert temporaries < 3.6 * GiB, temporaries / GiB
    tree = 4 * builder.param_count(cfg)
    assert tree == 4 * 509_661_185
    assert 6 * tree < _footprint(looped) < 15.0 * GiB, _footprint(looped)


def test_selected_attention_cell_step_fits_the_chip(one_chip):
    """``keye-vl-2.0-30b-a3b.steady-1g-8k`` as ``benchmarks/`` builds it:
    the fused one-group step (not donated, adamw) at the published widths,
    layers 0-3, 16 of 128 experts held and one sequence of 8,192 tokens. A
    layer's sparse attention is four Mosaic kernels (``sparse_select``,
    ``flash_fwd_sparse``, the fused ``flash_bwd_sparse``, ``indexer_loss``)
    and no dense flash kernel; the index scores and the attention's
    probabilities exist a tile, in VMEM: NO float32 ``[8192,8192]`` and no
    ``[32,8192,8192]`` of any type in the step, only each layer's selection,
    int8 ``[1,8192,8192]``, kept from its forward to its backward. The
    whole under 15.0 GiB (read 11.30: 5.20 in, 5.20 out, 0.90 of
    temporaries): the number ISSUE 60's fallback rule (8 experts held)
    reads, and does not trigger."""
    c, builder, cfg = _cell_fused_step("keye-vl-2.0-30b-a3b.steady-1g-8k",
                                       one_chip)
    text = c.as_text()
    layers = int(cfg["num_hidden_layers"])
    for kernel in ("%sparse_select", "%flash_fwd_sparse", "%flash_bwd_sparse",
                   "%indexer_loss"):
        calls = [line for line in text.splitlines()
                 if line.lstrip().startswith(kernel)
                 and "custom-call(" in line]
        assert len(calls) == layers, (kernel, len(calls))
    assert "flash_bwd_sparse_dq" not in text and "%attn" not in text
    assert "gmm" in text and "bf16[16,2048,768]" in text
    assert "f32[8192,8192]" not in text and "f32[1,8192,8192]" not in text
    assert "32,8192,8192]" not in text
    assert "s8[1,8192,8192]" in text
    tree = 4 * builder.param_count(cfg)
    assert tree == 4 * 465_391_104
    assert 6 * tree < _footprint(c) < 15.0 * GiB, _footprint(c) / GiB
