"""Decoder-only transformer (Llama-style) — the flagship distributed model
(BASELINE.md config 3: shard-within-group + replicate-across-groups).

TPU-first design:
- bfloat16 activations/matmuls (MXU-native), float32 params and softmax.
- RMSNorm + rotary positions + SwiGLU (the Llama recipe), head_dim and
  hidden sizes kept MXU-tile friendly (multiples of 128).
- No python-level branching on data inside ``__call__`` — trace-once,
  static shapes, fused by XLA.
- TP/SP-aware: :func:`tp_rules` gives the tensor-parallel PartitionSpecs
  (megatron column/row split pairs); attention can route through the ring
  primitive in :mod:`torchft_tpu.parallel.ring_attention` for sequence
  parallelism over long contexts.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    num_layers: int = 4
    embed_dim: int = 512
    num_heads: int = 8
    num_kv_heads: Optional[int] = None  # GQA; None = MHA
    hidden_dim: Optional[int] = None    # None → ~8/3 * embed, rounded to 128
    max_seq_len: int = 2048
    rope_theta: float = 10_000.0
    dtype: Any = jnp.bfloat16
    # attention impl: None → plain softmax attention; otherwise a callable
    # (q, k, v, causal) -> out, e.g. ring attention under shard_map.
    attention_fn: Optional[Callable] = None
    # Mixture-of-experts: num_experts > 0 replaces the dense MLP with an
    # expert layer of models/moe.py: ``moe_dispatch="dense"`` is MoEMLP
    # (every expert computes every token; the expert dim shards over the
    # "ep" mesh axis), ``"routed"`` is RoutedMoEMLP (token-expert pairs
    # grouped by expert; ``moe_held=(first, count)`` names the share of the
    # experts this model holds, None = all).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_dispatch: str = "dense"
    moe_dim: Optional[int] = None        # an expert's width; None → mlp_dim
    moe_held: Optional[Tuple[int, int]] = None
    moe_shared_dim: int = 0              # the shared expert's width; 0: none
    moe_score: str = "sigmoid"           # routed dispatch only
    moe_route_norm: bool = True
    moe_route_scale: float = 1.0
    moe_shared_gate: bool = False        # sigmoid(u . w) on the shared expert
    moe_form: str = "swiglu"             # an expert's form, routed only:
    #                             "swiglu" | "reglu" | "relu2" (models/moe.py)
    # What a routed layer's router reads, in layers of a mixer and an MLP:
    # "mlp", the MLP's own normed input, or "mixer", the mixer's normed
    # input (a router placed before attention: its selection, weights and
    # gradient are that stream's, and no part of the routing waits for the
    # mixer; the experts still compute on the MLP's input).
    moe_route_input: str = "mlp"
    moe_dense_layers: int = 0            # leading layers that keep a dense MLP
    moe_interpret: Optional[bool] = None  # routed: Pallas interpreted (None:
    #                                       off a TPU)
    # Layers of different kinds. ``layer_types[i]`` is "full_attention",
    # "sliding_attention" (key j visible to query i iff 0 <= i - j <
    # ``sliding_window``), "linear_attention" (a Gated DeltaNet mixer,
    # models/linear_attention.py, at the ``linear_*`` sizes below) or "conv"
    # (a gated short convolution, models/short_conv.py, of
    # ``linear_conv_kernel`` taps): a mixer and an MLP, two norms; or one of
    # the blocks that are ONE norm and ONE sub-block with the residual around
    # it: "mamba" (a Mamba-2 mixer, models/mamba2.py, at the ``ssm_*`` sizes
    # and ``linear_conv_kernel``), "moe" (the expert MLP alone) and
    # "attention" (full attention alone).
    # None = every layer full. ``rope_full_layers=False`` leaves rotary off
    # the full-attention layers; ``rotary_dim`` turns only the leading
    # ``rotary_dim`` dims of a head (None: the whole head).
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: Optional[int] = None
    rope_full_layers: bool = True
    rotary_dim: Optional[int] = None
    linear_key_heads: int = 0
    linear_key_dim: int = 0
    linear_value_heads: int = 0
    linear_value_dim: int = 0
    linear_conv_kernel: int = 4
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    # The attention block's options, all off in the Llama recipe: a stated
    # head size (None → embed_dim / num_heads), RMSNorm of queries and keys
    # per head, a sigmoid gate on the attention output, a second norm on
    # each sub-block's output (four a layer), the embedding times √embed_dim.
    attn_head_dim: Optional[int] = None
    qk_norm: bool = False
    attn_gate: bool = False
    sandwich_norm: bool = False
    embed_scale: bool = False
    rms_norm_eps: float = 1e-5
    # The head reads the embedding table: no ``lm_head`` in the tree, and the
    # one ``[vocab, embed]`` leaf takes the gather's gradient and the head's
    # (:func:`head_kernel`).
    tie_embeddings: bool = False
    # Latent attention (models/mla.py), on when ``kv_lora_rank`` is set:
    # queries through a ``q_lora_rank`` bottleneck, keys and values through
    # one of ``kv_lora_rank`` (an RMSNorm inside each), a query/key head of
    # ``qk_nope_head_dim`` dims without position and ``qk_rope_head_dim``
    # rotary dims whose key is ONE head shared by every query head, and a
    # value head of ``v_head_dim``. ``rope_interleave``: rotary turns the
    # pairs (2i, 2i+1) and not (i, i + d/2).
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    # Multi-token prediction: ``mtp_layers`` (0 or 1) modules after the
    # trunk, each a whole decoder layer of its own over [the next token's
    # embedding ; the trunk's output], read through the trunk's embedding
    # and head (``Transformer.__call__(..., return_mtp=True)``,
    # :func:`mtp_causal_lm_loss`).
    mtp_layers: int = 0
    # A looped stack: the ``num_layers`` layers and the final norm run
    # ``loop_steps`` times over ONE set of leaves, each pass reading the
    # pass before (the first the embedding), as one scan over the passes
    # whose body is one pass (``return_exits=True`` hands every pass's
    # output out, :func:`looped_causal_lm_loss`). ``exit_gate``: a learned
    # gate ``h . w + b`` on each exit (one float32 leaf, ``exit_gate/kernel``
    # [E + 1, 1], the bias its last row), whose exit distribution weights
    # the exits' losses. At 1 and False the model is the plain stack, the
    # same names and the same program.
    loop_steps: int = 1
    exit_gate: bool = False
    # A learned sparse attention, on when ``sparse_topk`` > 0: every
    # full-attention layer grows an ``indexer`` (``indexer_heads`` query
    # heads of ``indexer_head_dim`` on ONE key head, a ReLU and a learned
    # weight a head, reading a STOPPED copy of the layer's normed input),
    # each query attends to the ``min(t + 1, sparse_topk)`` earlier keys of
    # largest index score (``ops/sparse_index.py``,
    # ``ops.flash_attention.sparse_flash_attention``; ``attention_fn`` is
    # not asked), and the layer sows the indexer's loss (``indexer_loss``
    # collection; :func:`sparse_lm_loss` adds them). ``sparse_interpret``:
    # those kernels interpreted (None: off a TPU), as ``moe_interpret``.
    sparse_topk: int = 0
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    sparse_interpret: Optional[bool] = None
    # Per-layer rematerialization (``nn.remat`` of every layer). In a stack
    # that runs once it frees NOTHING on the chip (PERF.md section 7): with
    # ``prevent_cse=False`` outside a scan XLA merges the rematerialised
    # forward with the first one and keeps its activations, so it is no
    # switch to reach for when a model that runs once is short of memory.
    # Inside the pass scan of ``loop_steps > 1`` it is real: a pass keeps
    # each layer's input and the backward pass recomputes the layer.
    remat: bool = False

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.embed_dim // self.num_heads

    def layer_type(self, i: int) -> str:
        return self.layer_types[i] if self.layer_types else FULL

    def is_moe_layer(self, i: int) -> bool:
        if self.layer_type(i) in SINGLE:
            return self.layer_type(i) == EXPERTS
        return self.moe_experts > 0 and i >= self.moe_dense_layers

    @property
    def moe_layers(self) -> int:
        """Expert layers a step runs, a prediction module's among them."""
        return sum(self.is_moe_layer(i) for i in range(self.num_layers)) \
            + (self.mtp_layers if self.moe_experts else 0)

    @property
    def sparse_layers(self) -> int:
        """Layers whose attention goes through a selection."""
        if not self.sparse_topk:
            return 0
        return sum(self.layer_type(i) in (FULL, ATTENTION)
                   for i in range(self.num_layers))

    @property
    def mlp_dim(self) -> int:
        if self.hidden_dim is not None:
            return self.hidden_dim
        h = int(self.embed_dim * 8 / 3)
        return (h + 127) // 128 * 128


FULL, SLIDING = "full_attention", "sliding_attention"
LINEAR = "linear_attention"
CONV = "conv"
# blocks of one norm and one sub-block
MAMBA, EXPERTS, ATTENTION = "mamba", "moe", "attention"
SINGLE = (MAMBA, EXPERTS, ATTENTION)


def _norm(cfg: "TransformerConfig", name: str) -> "RMSNorm":
    return RMSNorm(eps=cfg.rms_norm_eps, name=name)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(x.dtype)


def _rotary_angles(positions: jnp.ndarray, d: int,
                   theta: float) -> jnp.ndarray:
    """``position * theta ** (-2i / d)`` for the ``d / 2`` pairs: float32
    [B, S, d/2]."""
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    return positions[..., None].astype(jnp.float32) * freqs


def rotary(x: jnp.ndarray, positions: jnp.ndarray,
           theta: float) -> jnp.ndarray:
    """Apply rotary position embedding. x: [B, S, H, D]."""
    angles = _rotary_angles(positions, x.shape[-1], theta)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def rotary_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                       theta: float) -> jnp.ndarray:
    """Rotary over adjacent pairs. x: [B, S, H, D]: the pair
    ``(x[2i], x[2i+1])`` turns by ``position * theta ** (-2i / D)``
    (:func:`rotary` pairs ``i`` with ``i + D/2``). Written as
    ``x * cos + swap(x) * sin`` with ``swap`` exchanging the two of a pair
    (two lane rolls and a select), so no [.., D/2, 2] layout is made."""
    d = x.shape[-1]
    angles = _rotary_angles(positions, d, theta)
    cos = jnp.repeat(jnp.cos(angles), 2, axis=-1)[:, :, None, :]
    sin = jnp.repeat(jnp.sin(angles), 2, axis=-1)[:, :, None, :]
    even = jnp.arange(d) % 2 == 0
    x32 = x.astype(jnp.float32)
    swapped = jnp.where(even, -jnp.roll(x32, -1, axis=-1),
                        jnp.roll(x32, 1, axis=-1))
    return (x32 * cos + swapped * sin).astype(x.dtype)


def _rotary_leading(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
                    rotary_dim: Optional[int]) -> jnp.ndarray:
    """:func:`rotary` over the first ``rotary_dim`` dims of each head (its
    halves paired, angles ``theta ** (-2i / rotary_dim)``); the rest pass."""
    if not rotary_dim or rotary_dim == x.shape[-1]:
        return rotary(x, positions, theta)
    return jnp.concatenate(
        [rotary(x[..., :rotary_dim], positions, theta), x[..., rotary_dim:]],
        axis=-1)


def plain_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None):
    """Reference softmax attention; q: [B, S, H, D], k/v may carry fewer
    (GQA) heads — repeated here (f32 softmax) — and v another head size
    than q and k. ``window`` (causal only): key j is visible to query i
    iff 0 <= i - j < window."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((s_q, s_k), dtype=bool),
                              k=s_k - s_q - int(window))
        logits = jnp.where(mask, logits, -1e30)
    elif window is not None:
        raise ValueError("a window needs causal=True")
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


plain_attention.supports_gqa = True


class LayerNorm(nn.Module):
    """Mean and variance over the last axis in float32, a gain and a bias."""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        norm = centred * jax.lax.rsqrt(
            jnp.mean(centred * centred, axis=-1, keepdims=True) + self.eps)
        return (norm * scale + bias).astype(x.dtype)


SPARSE_COUNTERS = ("sparse_selected_keys_milli_total",
                   "indexer_kl_micro_total")


class Indexer(nn.Module):
    """A sparse attention's indexer over the layer's normed input, whose
    gradient it stops (the indexer learns from its own loss and moves
    nothing upstream): ``(a, b, u)``, the index queries [B, S, J, c], the
    ONE index key head [B, S, c] through a LayerNorm, both under rotary
    (half-split, the whole ``c`` dims), and the heads' weights [B, S, J]
    in float32. Leaves ``a``, ``b``, ``b_norm``, ``u``."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, h, positions):
        cfg = self.cfg
        g = jax.lax.stop_gradient(h)
        heads, dim = cfg.indexer_heads, cfg.indexer_head_dim
        a = nn.DenseGeneral((heads, dim), use_bias=False, dtype=cfg.dtype,
                            name="a")(g)
        b = nn.Dense(dim, use_bias=False, dtype=cfg.dtype, name="b")(g)
        b = LayerNorm(eps=cfg.rms_norm_eps, name="b_norm")(b)
        a = rotary(a, positions, cfg.rope_theta)
        b = rotary(b[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
        u = nn.Dense(heads, use_bias=False, dtype=cfg.dtype, name="u")(g)
        return a, b, u.astype(jnp.float32)


def _selected_attention(module, x, q, k, v, positions):
    """Attention over the keys the layer's indexer selects, inside
    ``module`` (an :class:`Attention` being called): sows the indexer's loss
    there and returns ``(out, counts)``."""
    from torchft_tpu.ops.flash_attention import sparse_flash_attention
    from torchft_tpu.ops.sparse_index import indexer_kl, select_keys

    cfg = module.cfg
    interpret = cfg.sparse_interpret
    with jax.named_scope("sparse_index"):
        a, b, u = Indexer(cfg, name="indexer")(x, positions)
    with jax.named_scope("sparse_select"):
        selection, index_lse = select_keys(a, b, u, cfg.sparse_topk,
                                           interpret=interpret)
    with jax.named_scope("sparse_attn"):
        out, lse = sparse_flash_attention(
            q, k, v, selection, interpret=interpret, return_lse=True)
    with jax.named_scope("indexer_loss"):
        kl = indexer_kl(a, b, u, q, k, lse, selection, index_lse,
                        interpret=interpret)
    module.sow("indexer_loss", "kl", kl)
    module.sow("intermediates", "selection", selection)
    # the step's mean over its sparse layers
    share = 1.0 / cfg.sparse_layers
    keys = jnp.mean(jnp.sum(selection.astype(jnp.float32), axis=-1))
    counts = dict(zip(SPARSE_COUNTERS, (
        keys * (1e3 * share),
        jax.lax.stop_gradient(kl) * (1e6 * share))))
    return out, counts


def _position_attention(cfg, q, k, v, sliding: bool):
    """Attention under a mask of positions (causal, or a window),
    through ``cfg.attention_fn``."""
    attn = cfg.attention_fn or plain_attention
    if (cfg.kv_heads != cfg.num_heads
            and not getattr(attn, "supports_gqa", False)):
        # GQA: repeat kv heads for impls that need equal head counts.
        # The flash kernel shares them via index maps instead — no
        # H/H_kv-times kv memory blowup.
        rep = cfg.num_heads // cfg.kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if sliding:
        return attn(q, k, v, True, window=int(cfg.sliding_window))
    return attn(q, k, v, True)


class Attention(nn.Module):
    cfg: TransformerConfig
    kind: str = FULL

    @nn.compact
    def __call__(self, x, positions):
        """The attention block's output; where the layer selects its keys
        (``cfg.sparse_topk``, a full layer) ``(output, counts)``."""
        cfg = self.cfg
        if self.kind not in (FULL, SLIDING):
            raise ValueError(f"unknown layer type {self.kind!r}")
        sliding = self.kind == SLIDING
        if sliding and not cfg.sliding_window:
            raise ValueError("a sliding_attention layer needs "
                             "sliding_window")
        B, S, _ = x.shape
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, dtype=cfg.dtype, name=name)
        q = dense((cfg.num_heads, cfg.head_dim), "q")(x)
        k = dense((cfg.kv_heads, cfg.head_dim), "k")(x)
        v = dense((cfg.kv_heads, cfg.head_dim), "v")(x)
        if cfg.qk_norm:
            q = _norm(cfg, "q_norm")(q)
            k = _norm(cfg, "k_norm")(k)
        if sliding or cfg.rope_full_layers:
            q = _rotary_leading(q, positions, cfg.rope_theta, cfg.rotary_dim)
            k = _rotary_leading(k, positions, cfg.rope_theta, cfg.rotary_dim)
        if cfg.sparse_topk and not sliding:
            out, counts = _selected_attention(self, x, q, k, v, positions)
        else:
            out, counts = _position_attention(cfg, q, k, v, sliding), None
        out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
        if cfg.attn_gate:
            gate = nn.Dense(cfg.num_heads * cfg.head_dim, use_bias=False,
                            dtype=cfg.dtype, name="gate")(x)
            out = out * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(out.dtype)
        out = nn.DenseGeneral(cfg.embed_dim, use_bias=False,
                              dtype=cfg.dtype, name="o")(out)
        return out if counts is None else (out, counts)


class MLPBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = nn.Dense(cfg.mlp_dim, use_bias=False, dtype=cfg.dtype,
                        name="gate")(x)
        up = nn.Dense(cfg.mlp_dim, use_bias=False, dtype=cfg.dtype,
                      name="up")(x)
        return nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                        name="down")(nn.silu(gate) * up)


def _mixer(cfg: TransformerConfig, kind: str, h, positions, counts: dict):
    """The mixer of ``kind`` over the normed stream ``h``, named ``attn``;
    what it has for the program counters goes into ``counts``."""
    if kind in (LINEAR, MAMBA, CONV):
        if kind == LINEAR:
            from torchft_tpu.models.linear_attention import (
                GDN_COUNTERS as names, GatedDeltaNet as counted)
        elif kind == MAMBA:
            from torchft_tpu.models.mamba2 import (
                SSD_COUNTERS as names, Mamba2Mixer as counted)
        else:
            from torchft_tpu.models.short_conv import (
                SCONV_COUNTERS as names, ShortConv as counted)
        # a count (chunks walked, tokens) and a reading (the mean log decay,
        # the output's rms)
        a, (count, reading) = counted(cfg, name="attn")(h, return_stats=True)
        # the step's mean over its layers of this kind, in millionths
        share = 1e6 / sum(t == kind for t in cfg.layer_types)
        counts.update(zip(names, (count, reading * share)))
        return a
    if cfg.kv_lora_rank:
        from torchft_tpu.models.mla import LatentAttention

        return LatentAttention(cfg, name="attn")(h, positions)
    a = Attention(cfg, kind=FULL if kind == ATTENTION else kind,
                  name="attn")(h, positions)
    if isinstance(a, tuple):       # a layer that selects its keys
        a, sparse = a
        counts.update(sparse)
    return a


def _mlp(cfg: TransformerConfig, moe: bool, u, counts: dict, route_on=None):
    """The dense MLP (``mlp``) or, with ``moe``, the expert layer (``moe``)
    over the normed stream ``u``; a routed layer's counts go into
    ``counts``. ``route_on``: the stream a routed layer's router reads
    where that is not ``u`` (``cfg.moe_route_input``)."""
    if moe and cfg.moe_dispatch == "routed":
        from torchft_tpu.models.moe import (ROUTE_AHEAD_COUNTER,
                                            RoutedMoEMLP, moe_counts)

        # The counts leave the (rematerialised) layer as values:
        # Transformer counts them once a step.
        m, stats = RoutedMoEMLP(
            num_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
            mlp_dim=cfg.moe_dim or cfg.mlp_dim, held=cfg.moe_held,
            shared_dim=cfg.moe_shared_dim,
            shared_gate=cfg.moe_shared_gate, score=cfg.moe_score,
            route_norm=cfg.moe_route_norm,
            route_scale=cfg.moe_route_scale, form=cfg.moe_form,
            dtype=cfg.dtype, interpret=cfg.moe_interpret, name="moe")(
                u, route_on=route_on, return_stats=True)
        counts.update(moe_counts(stats, layers=cfg.moe_layers))
        if route_on is not None:
            counts[ROUTE_AHEAD_COUNTER] = jnp.int32(1)
        return m
    if route_on is not None:
        raise ValueError("moe_route_input='mixer' is the routed expert "
                         "layer's (moe_dispatch='routed')")
    if cfg.moe_form != "swiglu":
        raise ValueError(f"moe_form {cfg.moe_form!r} is the routed expert "
                         "layer's (moe_dispatch='routed' on a layer with "
                         "experts); every other MLP here is a SwiGLU")
    if moe and cfg.moe_dispatch == "dense":
        from torchft_tpu.models.moe import MoEMLP

        return MoEMLP(num_experts=cfg.moe_experts,
                      mlp_dim=cfg.moe_dim or cfg.mlp_dim,
                      top_k=cfg.moe_top_k, dtype=cfg.dtype, name="moe")(u)
    if moe:
        raise ValueError(f"unknown moe_dispatch {cfg.moe_dispatch!r}")
    return MLPBlock(cfg, name="mlp")(u)


class DecoderLayer(nn.Module):
    """One layer. Of most kinds: a mixer (attention, the Gated DeltaNet of
    ``"linear_attention"`` or the gated short convolution of ``"conv"``)
    and a dense or (``moe``) an expert MLP, pre-norm; with
    ``cfg.sandwich_norm`` each sub-block's output is normed again before it
    joins the stream; with ``cfg.moe_route_input="mixer"`` a routed expert
    layer's router reads the mixer's normed input, which the layer carries
    past the mixer. Of a kind in ``SINGLE``: one norm
    (``norm``) and one sub-block, ``x + F(norm(x))``, ``F`` a Mamba-2 mixer,
    the expert MLP or full attention. Returns the stream, and where the
    layer has numbers for the program counters (a routed expert layer, a
    linear-attention, mamba or conv mixer) ``(stream, {counter: value})``."""

    cfg: TransformerConfig
    kind: str = FULL
    moe: bool = False

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        counts = {}
        if cfg.moe_route_input not in ("mlp", "mixer"):
            raise ValueError(f"unknown moe_route_input "
                             f"{cfg.moe_route_input!r}; 'mlp' or 'mixer'")
        if self.kind in SINGLE:
            u = _norm(cfg, "norm")(x)
            if self.kind == EXPERTS:
                if not cfg.moe_experts:
                    raise ValueError('a "moe" layer needs moe_experts')
                if cfg.moe_route_input == "mixer":
                    raise ValueError('a "moe" block has no mixer for '
                                     "moe_route_input='mixer' to read "
                                     "before")
                x = x + _mlp(cfg, True, u, counts)
            else:
                x = x + _mixer(cfg, self.kind, u, positions, counts)
            return (x, counts) if counts else x
        h = _norm(cfg, "attn_norm")(x)
        a = _mixer(cfg, self.kind, h, positions, counts)
        if cfg.sandwich_norm:
            a = _norm(cfg, "post_attn_norm")(a)
        x = x + a
        ahead = self.moe and cfg.moe_route_input == "mixer"
        m = _mlp(cfg, self.moe, _norm(cfg, "mlp_norm")(x), counts,
                 route_on=h if ahead else None)
        if cfg.sandwich_norm:
            m = _norm(cfg, "post_mlp_norm")(m)
        return (x + m, counts) if counts else x + m


def _add_counts(total: Optional[dict], counts: dict) -> dict:
    total = dict(total or {})
    for key, value in counts.items():
        total[key] = total[key] + value if key in total else value
    return total


class MTPModule(nn.Module):
    """One multi-token-prediction module: position ``i`` reads the trunk's
    output there and the embedding of token ``i + 1`` (``next_embed``, the
    trunk's own table), ``z = [N_e(embedding) ; N_h(hidden)] W`` (2E -> E),
    one whole decoder layer with weights of its own, a final norm. What it
    returns goes through the trunk's head against token ``i + 2``. Returns
    ``(z, counts)``: a routed expert layer's ``{counter: value}``, else
    None."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, hidden, next_embed, positions):
        cfg = self.cfg
        z = jnp.concatenate([_norm(cfg, "embed_norm")(next_embed),
                             _norm(cfg, "hidden_norm")(hidden)], axis=-1)
        z = nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                     name="proj")(z)
        layer_cls = (nn.remat(DecoderLayer, prevent_cse=False)
                     if cfg.remat else DecoderLayer)
        z = layer_cls(cfg, kind=FULL, moe=cfg.moe_experts > 0,
                      name="block")(z, positions)
        z, stats = z if isinstance(z, tuple) else (z, None)
        return _norm(cfg, "final_norm")(z), stats


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False,
                 return_mtp: bool = False, return_exits: bool = False):
        """``return_hidden=True`` skips the LM head and returns the
        final-norm hidden states [B, S, E] — pair with
        :func:`chunked_causal_lm_loss` so the [B, S, vocab] logits tensor
        (the largest allocation in LM training; ~2 GB at B=16 S=2048
        V=32k in f32) never materializes.

        ``return_mtp=True`` (a model with ``mtp_layers``) returns
        ``(hidden, mtp_hidden, moe_stats)``: the trunk's final-norm states,
        the prediction module's (position ``i`` predicts token ``i + 2``;
        the last position has no next token and reads the first one's
        embedding, which causal attention keeps from every other), and the
        layers' summed counts as ``{counter: value}`` (``None`` where no
        layer has any), which are then NOT counted here: the caller counts
        them with whatever else it counts (:func:`mtp_causal_lm_loss`).

        ``return_exits=True`` (a looped stack, ``loop_steps`` passes)
        returns ``(exits, gate_logits)``: every pass's final-norm states
        stacked ``[T, B, S, E]`` and, with ``exit_gate``, the gate's float32
        logits on the exits of the passes before the last ``[T - 1, B, S]``
        (else ``None``). With ``loop_steps > 1`` ``return_hidden`` and the
        logits are the last pass's."""
        cfg = self.cfg
        if return_mtp and cfg.mtp_layers != 1:
            raise ValueError("return_mtp needs a model with mtp_layers=1, "
                             f"got {cfg.mtp_layers}")
        looped = cfg.loop_steps > 1 or cfg.exit_gate or return_exits
        if looped and (return_mtp or cfg.loop_steps < 1):
            raise ValueError("a looped stack has loop_steps >= 1 and no "
                             "prediction module")
        embed = nn.Embed(cfg.vocab_size, cfg.embed_dim,
                         dtype=cfg.dtype, name="embed")
        x = embed(tokens)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.embed_dim ** 0.5, x.dtype)
        if cfg.layer_types and len(cfg.layer_types) != cfg.num_layers:
            raise ValueError(f"{len(cfg.layer_types)} layer_types for "
                             f"{cfg.num_layers} layers")
        if looped:
            return self._looped(embed, x, tokens.shape, return_hidden,
                                return_exits)
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1]), tokens.shape)
        layer_cls = (nn.remat(DecoderLayer, prevent_cse=False)
                     if cfg.remat else DecoderLayer)
        moe_stats = None
        for i in range(cfg.num_layers):
            x = layer_cls(cfg, kind=cfg.layer_type(i),
                          moe=cfg.is_moe_layer(i),
                          name=f"layer_{i}")(x, positions)
            if isinstance(x, tuple):
                x, stats = x
                moe_stats = _add_counts(moe_stats, stats)
        if moe_stats is not None and not return_mtp:
            from torchft_tpu import tracing

            tracing.count_in_program(**moe_stats)
        x = _norm(cfg, "final_norm")(x)
        if return_mtp:
            nxt = embed(jnp.roll(tokens, -1, axis=1))
            if cfg.embed_scale:
                nxt = nxt * jnp.asarray(cfg.embed_dim ** 0.5, nxt.dtype)
            with jax.named_scope("mtp"):
                z, stats = MTPModule(cfg, name="mtp")(x, nxt, positions)
            if stats is not None:
                moe_stats = _add_counts(moe_stats, stats)
            return x, z, moe_stats
        if return_hidden:
            return x
        # the head in f32 for a stable loss: the embedding table read again
        # (``tie_embeddings``), else a kernel of its own
        return self._head(embed, x)

    def _head(self, embed, x):
        if self.cfg.tie_embeddings:
            return x.astype(jnp.float32) @ embed.embedding.T
        return nn.Dense(self.cfg.vocab_size, use_bias=False,
                        dtype=jnp.float32, name="lm_head")(x)

    def _looped(self, embed, x, shape, return_hidden, return_exits):
        """The stack as ONE scan over ``loop_steps`` passes whose body is
        one pass: the layers, then the final norm, over one set of leaves
        (broadcast to every pass: no pass axis in the tree, the paths
        ``layer_i/...`` and ``final_norm/...`` of the plain stack). A
        pass's output is both its exit and the next pass's input. With
        ``cfg.remat`` each layer is rematerialised inside the body, where
        it is real: a pass keeps its layers' inputs and nothing else."""
        cfg = self.cfg
        layer_cls = (nn.remat(DecoderLayer, prevent_cse=False)
                     if cfg.remat else DecoderLayer)

        def one_pass(mdl, x):
            positions = jnp.broadcast_to(jnp.arange(shape[1]), shape)
            with jax.named_scope("loop_pass"):
                for i in range(cfg.num_layers):
                    x = layer_cls(cfg, kind=cfg.layer_type(i),
                                  moe=cfg.is_moe_layer(i), parent=mdl,
                                  name=f"layer_{i}")(x, positions)
                    if isinstance(x, tuple):
                        raise ValueError(
                            "a looped stack's layers hand no counts out of "
                            f"the pass scan; layer {i} "
                            f"({cfg.layer_type(i)!r}) has some")
                x = RMSNorm(eps=cfg.rms_norm_eps, parent=mdl,
                            name="final_norm")(x)
            return x, x

        x, exits = nn.scan(one_pass, variable_broadcast="params",
                           split_rngs={"params": False},
                           length=cfg.loop_steps)(self, x)
        logits = None
        if cfg.exit_gate:   # whatever is asked for: the leaf exists
            with jax.named_scope("loop_gate"):
                logits = ExitGate(name="exit_gate")(exits[:-1])
        if return_exits:
            return exits, logits
        return x if return_hidden else self._head(embed, x)


class ExitGate(nn.Module):
    """A looped stack's exit gate: ``h . w + b`` a position in float32 (a
    multiply and a sum, not a product the chip would round). ONE leaf,
    ``kernel`` ``[E + 1, 1]``: ``w`` and, its last row, ``b`` (the input
    read as ``[h ; 1]``). A bias leaf of its own is one number whose
    gradient, a mean over every position of terms of both signs, passes
    through zero from seed to seed, where no comparison relative to the
    leaf's own scale can hold it (PERF.md, PR 56)."""

    @nn.compact
    def __call__(self, h):
        w = self.param("kernel", nn.initializers.normal(0.02),
                       (h.shape[-1] + 1, 1))[:, 0]
        return jnp.sum(h.astype(jnp.float32) * w[:-1], axis=-1) + w[-1]


# --------------------------------------------------------------- presets
#
# Named configurations for the BASELINE.md model families. Sizes follow
# the published Llama-2 architecture table; ``llama2_7b`` is the HSDP
# target of BASELINE config 3 (shard-within-group via fsdp rules,
# replicate-across-groups via the FT manager).

def tiny_config(**overrides: Any) -> TransformerConfig:
    """Test-scale model: full architecture, trivial size."""
    cfg = dict(vocab_size=256, num_layers=2, embed_dim=128, num_heads=4,
               max_seq_len=256)
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def llama2_7b_config(**overrides: Any) -> TransformerConfig:
    """Llama-2 7B: 32 layers, 4096 embed, 32 heads, 11008 hidden,
    4k context (params ≈ 6.74e9; asserted by eval_shape in
    tests/test_parallel.py)."""
    cfg = dict(vocab_size=32_000, num_layers=32, embed_dim=4096,
               num_heads=32, hidden_dim=11_008, max_seq_len=4096)
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def llama2_13b_config(**overrides: Any) -> TransformerConfig:
    """Llama-2 13B: 40 layers, 5120 embed, 40 heads, 13824 hidden."""
    cfg = dict(vocab_size=32_000, num_layers=40, embed_dim=5120,
               num_heads=40, hidden_dim=13_824, max_seq_len=4096)
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def llama2_70b_config(**overrides: Any) -> TransformerConfig:
    """Llama-2 70B: 80 layers, 8192 embed, 64 heads (8 kv — GQA),
    28672 hidden."""
    cfg = dict(vocab_size=32_000, num_layers=80, embed_dim=8192,
               num_heads=64, num_kv_heads=8, hidden_dim=28_672,
               max_seq_len=4096)
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def tp_rules() -> list:
    """Megatron-style tensor-parallel PartitionSpecs for
    :func:`torchft_tpu.parallel.sharding.apply_rules`.

    Column-split the q/k/v/gate/up projections (output dim over ``tp``),
    row-split o/down (input dim over ``tp``) so each pair needs a single
    psum, which XLA inserts from the shardings. Embedding and lm_head shard
    the embed/vocab dim.
    """
    return [
        (r"attn/[qkv]/kernel", P(None, "tp", None)),
        (r"attn/gate/kernel", P(None, "tp")),
        (r"attn/o/kernel", P("tp", None)),
        # a mamba mixer: the fused input projection's columns and the
        # output projection's rows (XLA moves what the scan needs)
        (r"attn/in_proj/kernel", P(None, "tp")),
        (r"attn/out_proj/kernel", P("tp", None)),
        (r"mlp/(gate|up)/kernel", P(None, "tp")),
        (r"mlp/down/kernel", P("tp", None)),
        (r"embed/embedding", P(None, "tp")),
        (r"lm_head/kernel", P(None, "tp")),
    ]


def fsdp_extra_rules() -> list:
    """Rules for combined fsdp+tp: norm scales replicated explicitly."""
    return [(r"(norm|scale)", P())]


def causal_lm_loss(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Next-token cross-entropy, mean over all positions."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.mean(nll)


# ------------------------------------------------------- the head's loss
#
# The chip the chunk rule is sized for (TPU v5e, Google Cloud's system
# architecture table: 197 TFLOP/s bf16, 819 GB/s of HBM; the same two
# numbers as ``benchmarks/harness/peaks.py`` and ``bench.py``).
_MXU_FLOPS_PER_S = 197e12
_HBM_BYTES_PER_S = 819e9
# Tokens a chunk from which the head-gradient product is bound by the MXU
# and not by the f32 ``[hidden, vocab]`` accumulator: a chunk of T tokens is
# ``2 T E V`` operations against ``8 E V`` bytes (the accumulator read and
# written), whatever E and V. 963 on this chip.
_DW_RIDGE_TOKENS = int(-(-4 * _MXU_FLOPS_PER_S // _HBM_BYTES_PER_S))
# Most bytes of one f32 logits tile ``[batch, chunk, vocab]``; the fused
# body holds two or three such tiles at once. 1,024 tokens at a vocabulary
# of 32,000 are 125 MiB.
_LOGITS_TILE_BYTES = 256 << 20
_CHUNK_MULTIPLE = 256


def head_loss_chunk(batch: int, seq: int, vocab: int) -> int:
    """Positions a chunk of :func:`chunked_causal_lm_loss` when the caller
    names none, from the shapes alone, in multiples of 256: the fewest that
    give ``batch * chunk`` at least ``_DW_RIDGE_TOKENS`` tokens (measured
    on the chip, more buys little at batch 4 and costs at batch 1, and the
    tile grows with it), but no more than keep the f32 logits tile
    ``[batch, chunk, vocab]`` under ``_LOGITS_TILE_BYTES`` (a vocabulary
    over about 69,000 gets the smaller chunk) or than the sequence needs,
    and never fewer than 256."""
    def up(n):
        return -(-n // _CHUNK_MULTIPLE) * _CHUNK_MULTIPLE

    ridge = up(-(-_DW_RIDGE_TOKENS // batch))
    most = (_LOGITS_TILE_BYTES // (4 * vocab * batch)
            // _CHUNK_MULTIPLE * _CHUNK_MULTIPLE)
    return max(min(ridge, most, up(max(seq - 1, 1))), _CHUNK_MULTIPLE)


def _head_chunks(hidden, tokens, chunk_size):
    """``hidden[:, :-1]`` and its targets cut into chunks, leading axis the
    chunk: ``[n, b, chunk, e]``, ``[n, b, chunk]`` and the f32 mask of the
    positions that are not padding."""
    b, s, e = hidden.shape
    s1 = s - 1
    n_chunks = -(-s1 // chunk_size)
    pad = n_chunks * chunk_size - s1
    h = jnp.pad(hidden[:, :-1], ((0, 0), (0, pad), (0, 0)))
    t = jnp.pad(tokens[:, 1:], ((0, 0), (0, pad)))
    mask = jnp.pad(jnp.ones((b, s1), jnp.float32), ((0, 0), (0, pad)))
    hc = h.reshape(b, n_chunks, chunk_size, e).transpose(1, 0, 2, 3)
    tc = t.reshape(b, n_chunks, chunk_size).transpose(1, 0, 2)
    mc = mask.reshape(b, n_chunks, chunk_size).transpose(1, 0, 2)
    return hc, tc, mc


def _chunk_nll(h_c, t_c, w):
    """f32 log-softmax of a chunk's logits and its targets' NLL."""
    logits = jnp.einsum("bce,ev->bcv", h_c.astype(w.dtype), w,
                        preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, t_c[..., None], axis=-1)[..., 0]
    return logp, nll


def head_kernel(params: Any) -> jnp.ndarray:
    """The head's ``[embed, vocab]`` kernel of a ``Transformer``'s
    ``params`` (the ``init`` output or its ``"params"``), for the callers of
    ``return_hidden=True``: the ``lm_head`` kernel, or where the model is
    tied (no ``lm_head`` in the tree) the embedding table transposed, so
    that a gradient taken through it lands in the table's own leaf beside
    the gather's."""
    p = params["params"] if "params" in params else params
    if "lm_head" in p:
        return p["lm_head"]["kernel"]
    return p["embed"]["embedding"].T


def _head_operand(head_kernel, matmul_dtype):
    return head_kernel.astype(jnp.float32 if matmul_dtype is None
                              else matmul_dtype)


def _head_loss_alone(hidden, head_kernel, tokens, chunk_size, matmul_dtype):
    """The loss alone: one scan, one head product a chunk."""
    b, s, _ = hidden.shape
    w = _head_operand(head_kernel, matmul_dtype)

    def body(total, xs):
        h_c, t_c, m_c = xs
        _, nll = _chunk_nll(h_c, t_c, w)
        return total + jnp.sum(nll * m_c), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0),
                            _head_chunks(hidden, tokens, chunk_size))
    return total / (b * (s - 1))


def _head_loss_fwd(hidden, head_kernel, tokens, chunk_size, matmul_dtype):
    """The loss and, in the same scan, both head gradients at a cotangent
    of one: per chunk the logits once, ``dlogits = (softmax - onehot) *
    mask / (b * s1)`` in f32, ``dh_c = dlogits @ W^T`` stacked out and
    ``dW += h_c^T @ dlogits`` in an f32 carry. Three head products a chunk;
    the backward rule only scales."""
    from torchft_tpu import tracing

    b, s, e = hidden.shape
    s1 = s - 1
    v = head_kernel.shape[1]
    w = _head_operand(head_kernel, matmul_dtype)
    chunks = _head_chunks(hidden, tokens, chunk_size)
    # counted on the host, when the rule is traced: nothing in the step
    tracing.add_program_counters(
        head_loss_fused_traces_total=1,
        head_loss_chunks_traced_total=chunks[0].shape[0])

    def body(carry, xs):
        total, dw = carry
        h_c, t_c, m_c = xs
        logp, nll = _chunk_nll(h_c, t_c, w)
        hit = t_c[..., None] == jnp.arange(v, dtype=t_c.dtype)
        dlogits = (jnp.exp(logp) - hit) * (m_c / (b * s1))[..., None]
        dh_c = jnp.einsum("bcv,ev->bce", dlogits, w,
                          preferred_element_type=jnp.float32)
        dw = dw + jnp.einsum("bce,bcv->ev", h_c.astype(w.dtype), dlogits,
                             preferred_element_type=jnp.float32)
        return (total + jnp.sum(nll * m_c), dw), dh_c.astype(hidden.dtype)

    (total, dw), dh = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.zeros((e, v), jnp.float32)), chunks)
    dh = dh.transpose(1, 0, 2, 3).reshape(b, -1, e)[:, :s1]
    dh = jnp.pad(dh, ((0, 0), (0, 1), (0, 0)))
    return total / (b * s1), (dh, dw.astype(head_kernel.dtype))


def _head_loss_bwd(chunk_size, matmul_dtype, residuals, g):
    dh, dw = residuals
    return ((dh.astype(jnp.float32) * g).astype(dh.dtype),
            (dw.astype(jnp.float32) * g).astype(dw.dtype), None)


_head_loss = jax.custom_vjp(_head_loss_alone, nondiff_argnums=(3, 4))
_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def chunked_causal_lm_loss(hidden: jnp.ndarray, head_kernel: jnp.ndarray,
                           tokens: jnp.ndarray,
                           chunk_size: Optional[int] = None,
                           matmul_dtype: Any = None) -> jnp.ndarray:
    """Next-token cross-entropy WITHOUT materializing [B, S, vocab].

    The full-logits tensor is the largest allocation in LM training
    (B=16, S=2048, V=32k → 2 GB in f32, live through the log-softmax
    backward). This computes the head matmul + log-softmax per sequence
    chunk inside ONE scan, which peaks at a few [B, chunk, V] tiles; when
    the loss is differentiated the same scan yields both head gradients
    (a ``jax.custom_vjp``: no forward scan, no recomputed logits). Use
    with ``model.apply(params, tokens, return_hidden=True)`` and
    :func:`head_kernel` of the params (the ``lm_head`` kernel, or a tied
    model's table transposed).

    ``chunk_size``: positions a chunk; ``None`` takes
    :func:`head_loss_chunk` of the shapes.

    ``matmul_dtype``: input dtype for the head matmul (accumulation is
    always f32 and the log-softmax runs on f32 logits either way). The
    default keeps f32 inputs — exact; ``jnp.bfloat16`` runs the head
    matmul (~10% of a small-model step's FLOPs) at the MXU's full bf16
    rate, the same precision the body's matmuls already use.
    """
    if chunk_size is None:
        chunk_size = head_loss_chunk(hidden.shape[0], hidden.shape[1],
                                     head_kernel.shape[1])
    return _head_loss(hidden, head_kernel, tokens, chunk_size, matmul_dtype)


# ------------------------------------------- the head's loss, weighted
#
# ``_head_loss`` with the weight of a position an argument and the
# per-position loss a result: what a loss over several exits needs, whose
# weights (an exit distribution) are themselves differentiated.

def _weight_chunks(weights, n_chunks, chunk_size):
    """``weights`` [b, s1] cut as :func:`_head_chunks` cuts the positions,
    zeros where they pad: ``[n, b, chunk]``."""
    b, s1 = weights.shape
    padded = jnp.pad(weights, ((0, 0), (0, n_chunks * chunk_size - s1)))
    return padded.reshape(b, n_chunks, chunk_size).transpose(1, 0, 2)


def _rows(stacked, s1):
    """``[n, b, chunk, ...]`` scan outputs back to ``[b, s1, ...]``."""
    b = stacked.shape[1]
    joined = jnp.moveaxis(stacked, 0, 1)
    return joined.reshape((b, -1) + stacked.shape[3:])[:, :s1]


def _weighted_nll_alone(hidden, head_kernel, tokens, weights, chunk_size,
                        matmul_dtype):
    """The weighted sum and the per-position loss: one scan, one head
    product a chunk."""
    w = _head_operand(head_kernel, matmul_dtype)
    hc, tc, _ = _head_chunks(hidden, tokens, chunk_size)
    wc = _weight_chunks(weights, hc.shape[0], chunk_size)

    def body(total, xs):
        h_c, t_c, w_c = xs
        _, nll = _chunk_nll(h_c, t_c, w)
        return total + jnp.sum(nll * w_c), nll

    total, nll = jax.lax.scan(body, jnp.float32(0.0), (hc, tc, wc))
    return total, _rows(nll, weights.shape[1])


def _weighted_nll_fwd(hidden, head_kernel, tokens, weights, chunk_size,
                      matmul_dtype):
    """As :func:`_head_loss_fwd` with ``dlogits = (softmax - onehot) *
    weights``: the sum, the per-position loss and, in the same scan, both
    head gradients at a cotangent of one; three head products a chunk."""
    from torchft_tpu import tracing

    b, s, e = hidden.shape
    s1 = s - 1
    v = head_kernel.shape[1]
    w = _head_operand(head_kernel, matmul_dtype)
    hc, tc, _ = _head_chunks(hidden, tokens, chunk_size)
    wc = _weight_chunks(weights, hc.shape[0], chunk_size)
    # counted on the host, when the rule is traced: nothing in the step
    tracing.add_program_counters(
        head_loss_weighted_traces_total=1,
        head_loss_weighted_chunks_traced_total=hc.shape[0])

    def body(carry, xs):
        total, dw = carry
        h_c, t_c, w_c = xs
        logp, nll = _chunk_nll(h_c, t_c, w)
        hit = t_c[..., None] == jnp.arange(v, dtype=t_c.dtype)
        dlogits = (jnp.exp(logp) - hit) * w_c[..., None]
        dh_c = jnp.einsum("bcv,ev->bce", dlogits, w,
                          preferred_element_type=jnp.float32)
        dw = dw + jnp.einsum("bce,bcv->ev", h_c.astype(w.dtype), dlogits,
                             preferred_element_type=jnp.float32)
        return ((total + jnp.sum(nll * w_c), dw),
                (dh_c.astype(hidden.dtype), nll))

    (total, dw), (dh, nll) = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.zeros((e, v), jnp.float32)),
        (hc, tc, wc))
    dh = jnp.pad(_rows(dh, s1), ((0, 0), (0, 1), (0, 0)))
    nll = _rows(nll, s1)
    return (total, nll), (dh, dw.astype(head_kernel.dtype), nll)


def _weighted_nll_bwd(chunk_size, matmul_dtype, residuals, cotangents):
    # the per-position loss is handed out undifferentiated: its cotangent
    # is not read
    g, _ = cotangents
    dh, dw, nll = residuals
    return ((dh.astype(jnp.float32) * g).astype(dh.dtype),
            (dw.astype(jnp.float32) * g).astype(dw.dtype), None, nll * g)


_weighted_nll = jax.custom_vjp(_weighted_nll_alone, nondiff_argnums=(4, 5))
_weighted_nll.defvjp(_weighted_nll_fwd, _weighted_nll_bwd)


def chunked_weighted_nll(hidden: jnp.ndarray, head_kernel: jnp.ndarray,
                         tokens: jnp.ndarray, weights: jnp.ndarray,
                         chunk_size: Optional[int] = None,
                         matmul_dtype: Any = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(sum(weights * nll), nll)``: the next-token cross-entropy of every
    position, ``nll`` float32 ``[B, S - 1]``, and its sum under ``weights``
    (float32, the same shape), in ONE scan over chunks as
    :func:`chunked_causal_lm_loss` (the same chunk rule, the same three
    head products a chunk when differentiated, ``dW`` in one float32
    carry). The sum is differentiated in ``hidden``, ``head_kernel`` AND
    ``weights`` (whose gradient is ``nll``); ``nll`` itself is handed out
    with its gradient stopped, for what only reads it. At ``weights =
    1 / (B * (S - 1))`` the sum is :func:`chunked_causal_lm_loss`. Several
    hidden streams over one head ride as batch (``hidden`` ``[T * B, S,
    E]``, ``tokens`` tiled): one scan, one ``dW`` carry."""
    if chunk_size is None:
        chunk_size = head_loss_chunk(hidden.shape[0], hidden.shape[1],
                                     head_kernel.shape[1])
    total, nll = _weighted_nll(hidden, head_kernel, tokens, weights,
                               chunk_size, matmul_dtype)
    return total, jax.lax.stop_gradient(nll)


def exit_distribution(gate_logits: jnp.ndarray) -> Tuple[jnp.ndarray,
                                                         jnp.ndarray]:
    """``(q, log q)``, float32 ``[T, ...]``, of the gate's logits on the
    first ``T - 1`` exits ``[T - 1, ...]``: ``lambda_t = sigmoid(logit_t)``,
    ``lambda_T = 1``, ``q_t = lambda_t * prod_{j<t} (1 - lambda_j)``, the
    running product kept as a sum of logs (no ``log(0)``); ``sum_t q_t =
    1``."""
    z = gate_logits.astype(jnp.float32)
    log_keep = jax.nn.log_sigmoid(-z)            # log(1 - lambda_t)
    kept_before = jnp.cumsum(log_keep, axis=0) - log_keep
    log_q = jnp.concatenate(
        [jax.nn.log_sigmoid(z) + kept_before,
         jnp.sum(log_keep, axis=0, keepdims=True)], axis=0)
    return jnp.exp(log_q), log_q


def looped_causal_lm_loss(model: "Transformer", params: Any,
                          tokens: jnp.ndarray, beta: float,
                          gate_bias_shift: float = 0.0,
                          chunk_size: Optional[int] = None) -> jnp.ndarray:
    """The loss of a looped stack with an exit gate (``loop_steps`` = ``T``
    passes, ``exit_gate``): the mean over positions of ``sum_t q_t l_t -
    beta * H(q)``, ``l_t`` the next-token cross-entropy on pass ``t``'s
    exit through the one head, ``q`` the gate's exit distribution there
    (:func:`exit_distribution`) and ``H`` its entropy. The ``T`` exits ride
    :func:`chunked_weighted_nll` as batch: one loss scan and one ``dW``
    carry a step; the gate learns through the weights. ``gate_bias_shift``
    is added to every gate logit (a constant beside the learned bias).

    Under a collector (``tracing.collect_counts``) it counts
    ``loop_passes_total`` (+``T``), ``loop_expected_exit_milli_total`` (the
    step's mean exit ``sum_t t q_t`` x 1000: 1000 says every token leaves
    at the first pass, ``T`` x 1000 that the gate never opens),
    ``loop_exit_entropy_micro_total`` (its mean entropy x 1e6) and
    ``loss_exit_first_micro_total`` / ``loss_exit_last_micro_total`` (the
    unweighted mean loss on the first and on the last exit x 1e6: their gap
    is what the passes buy)."""
    from torchft_tpu import tracing

    exits, gate_logits = model.apply(params, tokens, return_exits=True)
    if gate_logits is None:
        raise ValueError("looped_causal_lm_loss weights the exits by the "
                         "model's gate: exit_gate=True")
    t, b, s, e = exits.shape
    with jax.named_scope("loop_gate"):
        # the last position has no next token
        q, log_q = exit_distribution(gate_logits[..., :-1] + gate_bias_shift)
        entropy = -jnp.sum(q * log_q, axis=0)
    with jax.named_scope("loop_exits_loss"):
        weighted, nll = chunked_weighted_nll(
            exits.reshape(t * b, s, e), head_kernel(params),
            jnp.tile(tokens, (t, 1)),
            (q / (b * (s - 1))).reshape(t * b, s - 1), chunk_size)
    nll = nll.reshape(t, b, s - 1)
    step = jnp.arange(1, t + 1, dtype=jnp.float32)[:, None, None]
    # the collector stops the gradient of what it is handed
    tracing.count_in_program(
        loop_passes_total=t,
        loop_expected_exit_milli_total=jnp.mean(
            jnp.sum(step * q, axis=0)) * 1e3,
        loop_exit_entropy_micro_total=jnp.mean(entropy) * 1e6,
        loss_exit_first_micro_total=jnp.mean(nll[0]) * 1e6,
        loss_exit_last_micro_total=jnp.mean(nll[-1]) * 1e6)
    return weighted - beta * jnp.mean(entropy)


def mtp_causal_lm_loss(model: "Transformer", params: Any,
                       tokens: jnp.ndarray, mtp_weight: float,
                       chunk_size: Optional[int] = None) -> jnp.ndarray:
    """``L_main + mtp_weight * L_mtp`` of a model with one multi-token
    prediction module: the trunk's next-token loss and the module's loss
    against the token after next (mean over the ``S - 2`` positions that
    have one), both through :func:`chunked_causal_lm_loss` and the one
    head kernel (:func:`head_kernel`), whose gradient is the sum of the two.

    Under a collector (``tracing.collect_counts``, which every trainer of
    this package wraps its loss in) the routed layers' ``moe_*`` counts (the
    module's layer included) and the two losses as
    ``loss_main_micro_total`` / ``loss_mtp_micro_total`` (each loss x 1e6,
    summed over steps) leave the step's program as one output and reach
    :func:`tracing.program_counters`; differentiated by hand
    (``jax.value_and_grad`` of this function alone) it counts nothing."""
    from torchft_tpu import tracing

    hidden, mtp_hidden, stats = model.apply(params, tokens, return_mtp=True)
    head = head_kernel(params)
    main = chunked_causal_lm_loss(hidden, head, tokens, chunk_size)
    with jax.named_scope("mtp"):
        mtp = chunked_causal_lm_loss(mtp_hidden[:, :-1], head, tokens[:, 1:],
                                     chunk_size)
    counts = stats or {}
    tracing.count_in_program(
        loss_main_micro_total=jax.lax.stop_gradient(main) * 1e6,
        loss_mtp_micro_total=jax.lax.stop_gradient(mtp) * 1e6, **counts)
    return main + mtp_weight * mtp


def sparse_lm_losses(model: "Transformer", params: Any, tokens: jnp.ndarray,
                     chunk_size: Optional[int] = None
                     ) -> Tuple[jnp.ndarray, list]:
    """``(L_lm, [L_I of each sparse layer])`` of a model with a learned
    sparse attention (``sparse_topk``): the next-token loss through
    :func:`chunked_causal_lm_loss` and the indexers' losses the layers sow,
    in layer order. The two have DISJOINT leaves: the selection is a hard
    set and the indexer reads a stopped stream, so ``L_lm`` reaches every
    leaf but the indexers', and each ``L_I`` its own indexer's alone."""
    variables = {
        "params": params["params"] if "params" in params else params}
    hidden, sown = model.apply(variables, tokens, return_hidden=True,
                               mutable=["indexer_loss"])
    layers = sown.get("indexer_loss", {})
    kls = [kl for i in range(model.cfg.num_layers)
           for kl in layers.get(f"layer_{i}", {}).get("attn", {}).get(
               "kl", ())]
    if len(kls) != model.cfg.sparse_layers or not kls:
        raise ValueError(
            f"sparse_lm_loss: {len(kls)} indexer losses sown for "
            f"{model.cfg.sparse_layers} sparse layers (sparse_topk="
            f"{model.cfg.sparse_topk}; a rematerialised layer sows none)")
    return (chunked_causal_lm_loss(hidden, head_kernel(params), tokens,
                                   chunk_size), kls)


def sparse_lm_loss(model: "Transformer", params: Any, tokens: jnp.ndarray,
                   indexer_weight: float = 1.0,
                   chunk_size: Optional[int] = None) -> jnp.ndarray:
    """``L_lm + indexer_weight * sum_layers L_I`` (:func:`sparse_lm_losses`):
    one step trains the model under its selection and every layer's indexer
    toward the attention it steers, and because the two objectives share no
    leaf the sum's gradient IS the two gradients side by side.

    Under a collector the layers count ``sparse_selected_keys_milli_total``
    (the step's mean over queries and sparse layers of the keys a query
    attends to, x 1000) and ``indexer_kl_micro_total`` (the mean ``L_I``
    over layers x 1e6) beside the routed layers' ``moe_*``; tracing a
    selected attention counts ``sparse_attn_traces_total`` on the host."""
    lm, kls = sparse_lm_losses(model, params, tokens, chunk_size)
    return lm + indexer_weight * sum(kls)


def moe_lm_loss(model: "Transformer", params: Any,
                tokens: jnp.ndarray) -> jnp.ndarray:
    """LM loss + accumulated MoE load-balance aux losses (from the
    ``aux_loss`` collection sown by :class:`~torchft_tpu.models.moe.MoEMLP`).

    Only the ``params`` collection is passed into apply: ``init`` on an MoE
    config also returns a stale init-time ``aux_loss`` collection, and
    feeding it back would double-count the aux values and turn them into
    trainable leaves with constant gradient 1. Callers can hand in either
    the full ``init`` output or just its ``params``."""
    variables = {
        "params": params["params"] if "params" in params else params
    }
    logits, aux = model.apply(variables, tokens, mutable=["aux_loss"])
    loss = causal_lm_loss(logits, tokens)
    for leaf in jax.tree_util.tree_leaves(aux):
        loss = loss + jnp.sum(leaf)
    return loss
