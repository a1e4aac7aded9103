"""Latent attention (the DeepSeek-V3 block): queries and keys/values each
pass through a low-rank bottleneck with an RMSNorm inside, a head's query
and key are ``[no-position part | rotary part]`` with the rotary key ONE
head shared by every query head, and the value head is narrower than the
query/key head (192 / 128 at the published sizes).

This is the expanded (training) form: keys and values are written out per
head and go through the flash kernel, which takes the two head sizes as
they are (``ops/flash_attention.py``). The shared rotary key is broadcast
to the heads beside each head's no-position key before the kernel (the
kernel reads one ``[S, d_qk]`` key per head). The latent cache and the
absorption of ``W_kvb`` into the query and output projections belong to a
decoding path, which this repository does not have.

With ``h`` the normed input, ``N`` RMSNorm:

    c_q = N(h W_qa);  q = c_q W_qb -> heads of [q_nope | q_rope]
    [c_kv | k_r] = h W_kva;  c_kv <- N(c_kv)
    [k_nope | v] per head = c_kv W_kvb
    rotary on q_rope (per head) and on k_r (one head)
    o = softmax(causal, [q_nope|q_rope] [k_nope|k_r]^T / sqrt(d_qk)) v
    out = o W_o
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchft_tpu.models.transformer import (RMSNorm, TransformerConfig,
                                            plain_attention, rotary,
                                            rotary_interleaved)
from torchft_tpu.ops.flash_attention import SAVED_NAMES


class _ExpandAndAttend(nn.Module):
    """Keys and values written out per head from the latent, and the
    attention over them. A module of its own so that it can be
    rematerialised apart from the layer (below)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, q, c_kv, k_r):
        cfg = self.cfg
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        B, S, H, _ = q.shape
        kv = nn.DenseGeneral((H, nope + cfg.v_head_dim), axis=-1,
                             use_bias=False, dtype=cfg.dtype,
                             name="kv_b")(c_kv)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (B, S, H, rope))],
            axis=-1)
        with jax.named_scope("mla_attn"):
            return (cfg.attention_fn or plain_attention)(
                q, k, kv[..., nope:], True)


class LatentAttention(nn.Module):
    """See the module docstring. **What lives from the forward to the
    backward** is the latent ``c_kv`` (``kv_lora_rank`` wide) and the one
    rotary key head, not the keys and values expanded from them (at the
    published sizes 8 MiB against 160 MiB a layer of 8192 tokens): the
    expansion and the attention are one ``jax.checkpoint`` region that
    keeps only what the flash kernel alone can make (its output and row
    logsumexp, ``ops.flash_attention.SAVED_NAMES``), so the backward
    recomputes one ``W_kvb`` product a layer (1.5 % of the step's
    operations) and runs no second forward kernel."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        nope, rope, d_v = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim)
        if not (cfg.q_lora_rank and nope and rope and d_v):
            raise ValueError(
                "latent attention needs q_lora_rank, qk_nope_head_dim, "
                "qk_rope_head_dim and v_head_dim beside kv_lora_rank")
        B, S, _ = x.shape
        H = cfg.num_heads
        turn = rotary_interleaved if cfg.rope_interleave else rotary

        def dense(feats, name):
            return nn.DenseGeneral(feats, axis=-1, use_bias=False,
                                   dtype=cfg.dtype, name=name)

        with jax.named_scope("mla_q"):
            c_q = RMSNorm(eps=cfg.rms_norm_eps, name="q_norm")(
                dense(cfg.q_lora_rank, "q_a")(x))
            q = dense((H, nope + rope), "q_b")(c_q)
            q = jnp.concatenate(
                [q[..., :nope], turn(q[..., nope:], positions,
                                     cfg.rope_theta)], axis=-1)
        with jax.named_scope("mla_kv"):
            kv = dense(cfg.kv_lora_rank + rope, "kv_a")(x)
            c_kv = RMSNorm(eps=cfg.rms_norm_eps, name="kv_norm")(
                kv[..., :cfg.kv_lora_rank])
            k_r = turn(kv[..., None, cfg.kv_lora_rank:], positions,
                       cfg.rope_theta)                       # [B,S,1,rope]
            out = nn.remat(
                _ExpandAndAttend, prevent_cse=True,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *SAVED_NAMES))(cfg, name="expand")(q, c_kv, k_r)
        return dense(cfg.embed_dim, "o")(out.reshape(B, S, H * d_v))
