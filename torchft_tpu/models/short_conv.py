"""The gated short convolution (LFM2's "conv" operator): the mixer of a
``DecoderLayer`` of kind ``"conv"``, 18 of LFM2-8B-A1B's 24.

With ``u`` the normed input of width ``E`` and ``K`` = ``cfg.
linear_conv_kernel`` taps:

    [B | C | z] = u W_in                       (E -> 3 E, chunks in that order)
    h = B * z                                  (the gate before)
    c_t = sum_{j<K} k_j * h_{t-K+1+j}          (causal, depthwise, no bias)
    y = C * c                                  (the gate after)
    out = y W_out                              (E -> E)

No activation, no norm, no bias, and no state along the sequence beyond the
``K - 1`` tokens the convolution reads back: what mixes tokens here is a
memory-bound pass, and the layer's operations are its two projections.
The projections take ``cfg.dtype`` inputs and accumulate in float32; the two
gates and the convolution are float32 inside (one fused pass over the three
streams) and ``cfg.dtype`` out.

The layer's two numbers for the program counters leave it as values
(``return_stats=True``), as ``Mamba2Mixer``'s do: tokens through the mixer,
and rms(``y``), the gated convolution's output before the projection (near
zero says the convolution path carries nothing).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchft_tpu.ops.gated_delta import causal_conv1d

SCONV_COUNTERS = ("shortconv_tokens_total", "shortconv_out_rms_micro_total")


class ShortConv(nn.Module):
    """Input [B, S, E] -> [B, S, E]; with ``return_stats`` also float32[2]:
    ``(tokens, rms(y))``. The kernel's taps from
    ``cfg.linear_conv_kernel``."""

    cfg: Any   # TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, return_stats: bool = False) -> Any:
        cfg = self.cfg
        b, s, e = x.shape
        with jax.named_scope("sconv_proj"):
            bcz = nn.Dense(3 * e, use_bias=False, dtype=cfg.dtype,
                           name="in_proj")(x)
        with jax.named_scope("sconv_conv"):
            weight = self.param("conv", nn.initializers.lecun_normal(),
                                (cfg.linear_conv_kernel, e))
            b_gate, c_gate, z = (t.astype(jnp.float32)
                                 for t in jnp.split(bcz, 3, axis=-1))
            y = c_gate * causal_conv1d(b_gate * z, weight)
        with jax.named_scope("sconv_out"):
            out = nn.Dense(e, use_bias=False, dtype=cfg.dtype,
                           name="out_proj")(y.astype(cfg.dtype))
        if not return_stats:
            return out
        stats = jnp.stack([
            jnp.float32(b * s),
            jax.lax.stop_gradient(jnp.sqrt(jnp.mean(jnp.square(y))))])
        return out, stats
