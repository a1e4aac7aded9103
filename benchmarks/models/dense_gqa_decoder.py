"""Builder ``dense_gqa_decoder``: a decoder-only transformer with pre-norm
RMSNorm, full rotary in the half-split convention, grouped-query causal
attention, SwiGLU, no biases and an untied head (Mistral-7B-v0.1,
InternLM2-1.8B). A configuration names this file by ``"builder"``.

What a builder gives the harness:

- ``make_loss_fn``: the *program's* model at the configuration's sizes;
- ``param_shapes``: the parameter tree, named as the program names it, so
  that one set of seeded weights can be handed to both sides;
- ``reference_loss``: the plain reference, written in straightforward
  ``jax.numpy`` and float32, independent of ``torchft_tpu.models`` (it shares
  the names of the tree and none of the code). Every place where a narrower
  type could be put passes through a named rounding site, the identity for
  the reference itself;
- ``CONTROLS`` / ``PROBES``: the reference computed in a lower precision, put
  in the program's place (``benchmarks/control.py`` reads them on the chip);
- ``param_count``, ``forward_flops_per_token``, ``train_flops_per_token``:
  what the algorithm needs, from shapes;
- ``REHEARSE``, ``REHEARSE_SEQ``: what ``--rehearse`` shrinks the sizes to.

Departures from the published models: none in the block; ``sliding_window``
(Mistral) equals full causal attention at the sequence lengths the cells use
and is not modelled.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Mapping, Optional

import jax
import jax.numpy as jnp

# What --rehearse shrinks the widths to. Never a cell; never a device number.
REHEARSE = dict(hidden_size=128, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=256, vocab_size=512)
REHEARSE_SEQ = 64

# The reference in a lower precision than the configuration states, put in
# the program's place: site -> type. "/forward" rounds the value and lets the
# gradient pass (narrow matmul inputs, wide gradients); without it the value
# and its cotangent are both rounded, as a computation carried out in that
# type would.
CONTROLS: Dict[str, Dict[str, str]] = {
    # the step below the bfloat16 that matmul inputs are stated in
    "fp8_matmul": {"matmul": "float8_e4m3/forward"},
}
# Computations that the comparison cannot tell from the stated precision
# (PERF.md section 2 has the readings): read beside the controls so that the
# next reader sees how far each is from the sound program, and not required
# to fail.
PROBES: Dict[str, Dict[str, str]] = {
    # the stated precision itself: bfloat16 matmul inputs and activations
    # (the matmul inputs forward only: rounding their cotangents as well
    # would already round every matrix's gradient leaf to bfloat16)
    "stated_bf16": {"matmul": "bfloat16/forward", "residual": "bfloat16"},
    # the float32 islands of the block lowered too: norms, softmax, logits
    # and log-softmax in bfloat16
    "bf16_islands": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                     "norm": "bfloat16", "softmax": "bfloat16",
                     "logits": "bfloat16"},
    # the stated precision with the gradient leaves rounded to bfloat16
    "bf16_grads": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                   "grads": "bfloat16"},
}


# ----------------------------------------------------- the program's model

def make_loss_fn(cfg: Mapping[str, Any], seq: int, interpret: bool
                 ) -> Callable:
    """The program's model at the configuration's sizes, with the flash
    kernel, per-layer remat and the chunked loss (as ``chip_smoke.py`` and
    ``examples/train_lm.py`` build it)."""
    from torchft_tpu.models import Transformer, chunked_causal_lm_loss
    from torchft_tpu.models.transformer import TransformerConfig
    from torchft_tpu.ops import flash_attention

    if abs(float(cfg["rms_norm_eps"]) - 1e-5) > 1e-12:
        raise ValueError("the program's RMSNorm has eps 1e-5 fixed; this "
                         f"configuration states {cfg['rms_norm_eps']}")
    tcfg = TransformerConfig(
        vocab_size=int(cfg["vocab_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        embed_dim=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        hidden_dim=int(cfg["intermediate_size"]),
        max_seq_len=seq, rope_theta=float(cfg["rope_theta"]),
        attention_fn=functools.partial(flash_attention, interpret=interpret),
        remat=True)
    model = Transformer(tcfg)

    def loss_fn(params, batch):
        hidden = model.apply(params, batch["tokens"], return_hidden=True)
        return chunked_causal_lm_loss(
            hidden, params["params"]["lm_head"]["kernel"], batch["tokens"])

    return loss_fn


# ------------------------------------------------------------- the shapes

def _w(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    heads = int(cfg["num_attention_heads"])
    return dict(E=int(cfg["hidden_size"]), H=heads,
                Hkv=int(cfg["num_key_value_heads"]),
                D=int(cfg["hidden_size"]) // heads,
                F=int(cfg["intermediate_size"]), V=int(cfg["vocab_size"]),
                L=int(cfg["num_hidden_layers"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


def param_shapes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    """The parameter tree's shapes (all float32), named as the program's
    ``Transformer`` names them. One-dimensional leaves are norm scales (made
    as ones), the others matrices (made normal(0, initializer_range))."""
    w = _w(cfg)
    layer = {
        "attn_norm": {"scale": (w["E"],)},
        "attn": {"q": {"kernel": (w["E"], w["H"], w["D"])},
                 "k": {"kernel": (w["E"], w["Hkv"], w["D"])},
                 "v": {"kernel": (w["E"], w["Hkv"], w["D"])},
                 "o": {"kernel": (w["H"] * w["D"], w["E"])}},
        "mlp_norm": {"scale": (w["E"],)},
        "mlp": {"gate": {"kernel": (w["E"], w["F"])},
                "up": {"kernel": (w["E"], w["F"])},
                "down": {"kernel": (w["F"], w["E"])}},
    }
    tree: Dict[str, Any] = {"embed": {"embedding": (w["V"], w["E"])},
                            "final_norm": {"scale": (w["E"],)},
                            "lm_head": {"kernel": (w["E"], w["V"])}}
    for i in range(w["L"]):
        tree[f"layer_{i}"] = layer
    return {"params": tree}


# ---------------------------------------------------- the plain reference

def _same(x):
    return x


def _rms_norm(x, scale, eps, r):
    x = r(x)
    mean_sq = r(jnp.mean(r(x * x), axis=-1, keepdims=True))
    return r(r(x * r(jax.lax.rsqrt(mean_sq + eps))) * scale)


def _rope(x, theta):
    """x: [B, S, H, D]; rotate pairs (i, i + D/2) by position * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v, mm, soft):
    """Causal softmax attention, q [B,S,H,D], k/v [B,S,Hkv,D]; one kv head's
    group of query heads at a time, so that the [S, S] scores of a 4096-token
    sequence stay a fraction of the chip's memory."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, S, Hkv, g, D).transpose(2, 0, 3, 1, 4)  # [Hkv,B,g,S,D]
    kg = k.transpose(2, 0, 1, 3)                               # [Hkv,B,S,D]
    vg = v.transpose(2, 0, 1, 3)
    mask = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def one(args):
        q1, k1, v1 = args
        s = soft(jnp.einsum("bgqd,bkd->bgqk", mm(q1), mm(k1)) * (D ** -0.5))
        p = soft(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1))
        return jnp.einsum("bgqk,bkd->bgqd", mm(p), mm(v1))

    out = jax.lax.map(one, (qg, kg, vg))                       # [Hkv,B,g,S,D]
    return out.transpose(1, 3, 0, 2, 4).reshape(B, S, H * D)


def reference_loss(params: Any, tokens: Any, cfg: Mapping[str, Any],
                   rounding: Optional[Mapping[str, Callable]] = None) -> Any:
    """Mean next-token cross-entropy of ``tokens`` [B, S] in float32 at the
    highest matmul precision. ``rounding`` maps a site to a function put on
    every value there: ``matmul`` (the inputs of every matrix product),
    ``residual`` (the embedding and the stream after each addition),
    ``norm`` (inside and after every RMSNorm), ``softmax`` (attention scores
    and probabilities), ``logits`` (logits and log-probabilities). A site
    that is not named is left in float32."""
    w = _w(cfg)
    r = dict(rounding or {})
    mm, res = r.get("matmul", _same), r.get("residual", _same)
    nrm, soft, lg = (r.get("norm", _same), r.get("softmax", _same),
                     r.get("logits", _same))
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = res(p["embed"]["embedding"][tokens])
        for i in range(w["L"]):
            lp = p[f"layer_{i}"]
            h = _rms_norm(x, lp["attn_norm"]["scale"], w["eps"], nrm)
            a = lp["attn"]
            q = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["q"]["kernel"]))
            k = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["k"]["kernel"]))
            v = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["v"]["kernel"]))
            o = _attention(_rope(q, w["theta"]), _rope(k, w["theta"]), v,
                           mm, soft)
            x = res(x + mm(o) @ mm(a["o"]["kernel"]))
            h = _rms_norm(x, lp["mlp_norm"]["scale"], w["eps"], nrm)
            m = lp["mlp"]
            gate = mm(h) @ mm(m["gate"]["kernel"])
            up = mm(h) @ mm(m["up"]["kernel"])
            x = res(x + mm(jax.nn.silu(gate) * up) @ mm(m["down"]["kernel"]))
        x = _rms_norm(x, p["final_norm"]["scale"], w["eps"], nrm)
        logits = lg(mm(x[:, :-1]) @ mm(p["lm_head"]["kernel"]))
        logp = lg(jax.nn.log_softmax(logits, axis=-1))
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return jnp.mean(nll)


# ------------------------------------------- what the algorithm needs
#
# A matrix multiplication of [m, k] by [k, n] is 2*m*k*n operations. Causal
# attention needs half the score matrix, so it counts half. Recomputation
# (``remat``, the flash backward's second pass over the scores) is work the
# implementation chose, not work the algorithm needs, and is not counted: a
# share of a peak computed from these can then only be understated.

def param_count(cfg: Mapping[str, Any]) -> int:
    """Parameters of the decoder as run: untied embedding and head, no
    biases, two norm scales a layer and a final one."""
    w = _w(cfg)
    layer = (w["E"] * w["H"] * w["D"] + 2 * w["E"] * w["Hkv"] * w["D"]
             + w["H"] * w["D"] * w["E"] + 3 * w["E"] * w["F"] + 2 * w["E"])
    return w["L"] * layer + 2 * w["V"] * w["E"] + w["E"]


def forward_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    """Forward operations for one token of a ``seq``-token causal sequence
    (the mean over its positions)."""
    w = _w(cfg)
    proj = 2 * w["E"] * (w["H"] * w["D"] + 2 * w["Hkv"] * w["D"]) \
        + 2 * w["H"] * w["D"] * w["E"]
    mlp = 3 * 2 * w["E"] * w["F"]
    # scores and weighted values: 2 matmuls of 2*S*D per head and token over
    # the whole square; causal needs half of it.
    attn = 2 * (2 * seq * w["D"] * w["H"]) / 2
    head = 2 * w["E"] * w["V"]
    return w["L"] * (proj + mlp + attn) + head


def train_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    """Forward and backward: the backward of a matmul is two matmuls."""
    return 3.0 * forward_flops_per_token(cfg, seq)
