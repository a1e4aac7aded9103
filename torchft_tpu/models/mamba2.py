"""Mamba-2: the state-space mixer of a hybrid decoder (23 of Nemotron-H's
52 blocks), a ``DecoderLayer`` of kind ``"mamba"``.

With ``u`` the normed input, ``H`` heads of ``P``, ``G`` groups and a state
of ``N`` a head (inner width ``H P``; the convolution runs over ``H P + 2 G
N`` channels):

    [z | xBC | dt] = u W_in                  (widths H P | H P + 2 G N | H)
    xBC <- SiLU(causal depthwise convolution, kernel ``K``, WITH a bias)
    [x | B | C] = xBC;  Delta = softplus(dt + dt_bias);  A = -exp(A_log)
    y = the state-space scan over (x, Delta, A, B, C, D)   (ops/ssd.py)
    y <- RMSNorm_group(y * SiLU(z)) * w_n;  out = y W_out

The gate is applied BEFORE the norm, and the norm's mean square is taken
over each group's ``H P / G`` channels. ``Delta``, the scan's decays and
states, the gate and the norm are float32.

The scan's residuals are kept, not recomputed under ``jax.checkpoint`` as
the delta rule's are: since PR 54 they are the scan's inputs and the state
each chunk found (float32 ``[B, chunks, H P, N]``: 0.13 GiB a block at the
benchmark cell's 1 x 8,192 tokens and published sizes, where the batched
products the kernels replaced kept or recomputed 0.7 of triangles and
per-chunk states), and what the step has no room for the chip's compiler
rematerialises by itself either way: under the cell's six trees of state it
reads 2.13 GiB of temporaries (2.29 before the kernels;
``tests/test_chip_compile.py``'s compile of the step, PERF.md, PR 54), and a
second forward scan under ``jax.checkpoint`` would buy back one state a
block.

The layer's two numbers for the program counters leave it as values
(``return_stats=True``), as ``GatedDeltaNet``'s do and for the same reason:
chunks walked, and the mean log decay ``Delta A`` a token (near 0 the state
keeps everything; below about -0.05 it has forgotten a chunk's start by the
chunk's end).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchft_tpu.models.linear_attention import _a_log_init, _dt_bias_init
from torchft_tpu.ops.gated_delta import causal_conv1d
from torchft_tpu.ops.ssd import CHUNK, ssd_scan

SSD_COUNTERS = ("ssd_chunks_total", "ssd_log_decay_micro_total")


class Mamba2Mixer(nn.Module):
    """Input [B, S, E] -> [B, S, E]; with ``return_stats`` also float32[2]:
    ``(chunks walked, mean Delta A)``. Sizes from ``cfg.ssm_*`` and
    ``cfg.linear_conv_kernel``."""

    cfg: Any   # TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, return_stats: bool = False) -> Any:
        cfg = self.cfg
        h, p = cfg.ssm_heads, cfg.ssm_head_dim
        g, n = cfg.ssm_groups, cfg.ssm_state
        if not (h and p and g and n) or h % g:
            raise ValueError(
                "a mamba layer needs ssm_heads (a multiple of ssm_groups), "
                f"ssm_head_dim, ssm_groups and ssm_state; got {h}, {p}, "
                f"{g}, {n}")
        b, s, _ = x.shape
        inner = h * p
        conv_ch = inner + 2 * g * n
        with jax.named_scope("ssd_proj"):
            zxbcdt = nn.Dense(inner + conv_ch + h, use_bias=False,
                              dtype=cfg.dtype, name="in_proj")(x)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_ch], axis=-1)
        with jax.named_scope("ssd_conv"):
            weight = self.param("conv", nn.initializers.lecun_normal(),
                                (cfg.linear_conv_kernel, conv_ch))
            bias = self.param("conv_bias", nn.initializers.zeros, (conv_ch,))
            xbc = nn.silu(causal_conv1d(xbc, weight, bias))
        a_log = self.param("A_log", _a_log_init, (h,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,))
        skip = self.param("D", nn.initializers.ones, (h,))
        with jax.named_scope("ssd_scan"):
            xs, b_in, c_in = jnp.split(xbc, [inner, inner + g * n], axis=-1)
            delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            a = -jnp.exp(a_log)
            y = ssd_scan(xs.reshape(b, s, h, p), delta, a,
                         b_in.reshape(b, s, g, n), c_in.reshape(b, s, g, n),
                         skip, cfg.dtype)
        with jax.named_scope("ssd_norm_out"):
            scale = self.param("norm", nn.initializers.ones, (inner,))
            y = y.reshape(b, s, inner) * nn.silu(z.astype(jnp.float32))
            y = y.reshape(b, s, g, inner // g)
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                                  + cfg.rms_norm_eps)
            y = (y.reshape(b, s, inner) * scale).astype(cfg.dtype)
            out = nn.Dense(x.shape[-1], use_bias=False, dtype=cfg.dtype,
                           name="out_proj")(y)
        if not return_stats:
            return out
        stats = jnp.stack([jnp.float32(b * -(-s // CHUNK)),
                           jax.lax.stop_gradient(jnp.mean(delta * a))])
        return out, stats
