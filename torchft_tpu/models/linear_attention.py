"""Gated DeltaNet: the linear-attention mixer of a hybrid decoder (three of
every four layers in Qwen3-Next), a ``DecoderLayer`` of kind
``"linear_attention"``.

With ``u`` the normed input, ``H_k`` key heads of ``d_k`` and ``H`` value
heads of ``d_v`` (a key head serves ``H / H_k`` value heads):

    [q | k | v | z] = u W_qkvz;  [b | a] = u W_ba
    [q | k | v] <- SiLU(causal depthwise convolution, kernel ``K``, no bias)
    q, k <- q / sqrt(sum q^2 + 1e-6), the same for k;  q <- q d_k^-1/2
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   (float32)
    o = the gated delta rule over (q, k, v, g, beta)   (ops/gated_delta.py)
    o <- RMSNorm_head(o) * w_n * SiLU(z);  out = [o] W_out

The state that the rule carries along the sequence is the only thing in the
model that does; ``ops/gated_delta.py`` says how it is computed in chunks
(batched products, one kernel for the chunk inverses, two for the chunks'
recurrence). The rule is rematerialised here (``jax.checkpoint``): what its
backward keeps is the state every chunk found, 256 MiB a layer at 8,192
tokens and the published sizes, and the chunk products' outputs, 0.35 GiB
more. Without the checkpoint the benchmark cell's step has one forward a
layer less to compute and 0.85 GiB more temporaries (4.58 -> 5.43 GiB by
the chip's compiler), and beside the six trees of a step that is not
donated it ran 3 % slower on the chip, not faster (31,316 -> 30,399
tokens/s at one seed; the plain donated loop read the other way, 250 ->
244 ms a step; PERF.md PR 49): it stays until the step holds fewer trees.
A region around everything between the two projections was tried in PR 40
and took more.

The layer's two numbers for the program counters leave it as values
(``return_stats=True``), since a count taken inside a rematerialised
layer is a value of that region and cannot leave the program from there
(``tracing.count_in_program``): chunks walked, and the mean log decay
``g`` (whether the state
still carries: near 0 it keeps everything, below about -0.1 a token it has
forgotten a chunk's start by the chunk's end).
"""

from __future__ import annotations

import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchft_tpu.ops.gated_delta import (CHUNK, causal_conv1d,
                                         gated_delta_rule)

GDN_COUNTERS = ("gdn_chunks_total", "gdn_log_decay_micro_total")


def _dt_bias_init(key: Any, shape: Tuple[int, ...], dtype: Any = jnp.float32):
    """The inverse softplus of a step drawn log-uniformly from
    [0.001, 0.1], so that a fresh layer decays by 0.1-10 % a token."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key: Any, shape: Tuple[int, ...], dtype: Any = jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


class GatedDeltaNet(nn.Module):
    """Input [B, S, E] -> [B, S, E]; with ``return_stats`` also float32[2]:
    ``(chunks walked, mean g)``. Sizes from ``cfg.linear_*``."""

    cfg: Any   # TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, return_stats: bool = False) -> Any:
        cfg = self.cfg
        hk, dk = cfg.linear_key_heads, cfg.linear_key_dim
        h, dv = cfg.linear_value_heads, cfg.linear_value_dim
        if not (hk and dk and h and dv) or h % hk:
            raise ValueError(
                "a linear_attention layer needs linear_key_heads, "
                "linear_key_dim, linear_value_heads (a multiple of the key "
                f"heads) and linear_value_dim; got {hk}, {dk}, {h}, {dv}")
        b, s, _ = x.shape
        conv_ch = 2 * hk * dk + h * dv
        with jax.named_scope("gdn_proj"):
            qkvz = nn.Dense(conv_ch + h * dv, use_bias=False,
                            dtype=cfg.dtype, name="in_qkvz")(x)
            ba = nn.Dense(2 * h, use_bias=False, dtype=cfg.dtype,
                          name="in_ba")(x)
        with jax.named_scope("gdn_conv"):
            weight = self.param("conv", nn.initializers.lecun_normal(),
                                (cfg.linear_conv_kernel, conv_ch))
            qkv = nn.silu(causal_conv1d(qkvz[..., :conv_ch], weight))
        a_log = self.param("A_log", _a_log_init, (h,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,))
        with jax.named_scope("gdn_scan"):
            q, k, v = jnp.split(qkv, [hk * dk, 2 * hk * dk], axis=-1)

            def unit(y):        # L2 over a head's dims, float32 inside
                y32 = y.reshape(b, s, hk, dk).astype(jnp.float32)
                return y32 * jax.lax.rsqrt(
                    jnp.sum(y32 * y32, axis=-1, keepdims=True) + 1e-6)

            q = (unit(q) * dk ** -0.5).astype(cfg.dtype)
            k = unit(k).astype(cfg.dtype)
            ba32 = ba.astype(jnp.float32)
            beta = jax.nn.sigmoid(ba32[..., :h])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba32[..., h:] + dt_bias)
            o = jax.checkpoint(gated_delta_rule, static_argnums=(5,))(
                q, k, v.reshape(b, s, h, dv), g, beta, cfg.dtype)
        with jax.named_scope("gdn_norm_out"):
            scale = self.param("norm", nn.initializers.ones, (dv,))
            z = qkvz[..., conv_ch:].reshape(b, s, h, dv).astype(jnp.float32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + cfg.rms_norm_eps)
            o = (o * scale * nn.silu(z)).astype(cfg.dtype)
            out = nn.Dense(x.shape[-1], use_bias=False, dtype=cfg.dtype,
                           name="out")(o.reshape(b, s, h * dv))
        if not return_stats:
            return out
        stats = jnp.stack([jnp.float32(b * -(-s // CHUNK)),
                           jax.lax.stop_gradient(jnp.mean(g))])
        return out, stats
