"""Builder ``lfm2_moe_decoder``: the ``lfm2_moe`` decoder (LFM2-8B-A1B):
two-norm layers whose mixer is a gated short convolution (``conv``) or
grouped-query attention with head norms and rotary (``full_attention``),
leading dense SwiGLU layers, then expert layers (sigmoid scores over all
experts, the top k normalised, no shared expert) of which **a share of the
routed experts** is held (``num_experts_held`` from ``first_expert_held``);
the head is the embedding table (tied). A configuration names this file by
``"builder"``.

The layers that run are ``published_layers`` (indices into the published
``layer_types``; one below ``num_dense_layers`` is dense). What a builder
gives the harness is listed in ``dense_gqa_decoder.py``; the reference's
rounding sites are that file's plus ``router`` (the router's matmul inputs
and its scores). ``drop_taps`` is a switch and no rounding, as
``drop_carry`` is in ``mamba2_moe_decoder.py``: the precision given is
ignored and the reference's convolution reads the current token only,
``c_t = k_{K-1} h_t`` (the control of that name: a convolution that lost its
history).

The equations, ``N`` RMSNorm (``y = w x / sqrt(mean x^2 + eps)``), layer
``i``: ``x <- x + F_i(N_op(x))``, then ``x <- x + G_i(N_ffn(x))``; no bias:

    conv: [B | C | z] = u W_in (E -> 3 E);  h = B * z
      c_t = sum_{j<K} k_j * h_{t-K+1+j}, zeros left of the sequence (K =
      conv_L_cache);  y = C * c;  F = y W_out.  No activation, no norm.
    full_attention: q = u W_q (H heads x D), k, v = u W_k, u W_v (H_kv x D);
      q, k <- N_head(q), N_head(k) (one gain of D for all heads), then
      rotary over the whole head, pairs (i, i + D/2), theta rope_theta;
      a = softmax_causal(q k^T / sqrt(D)) v;  F = a W_o
    dense MLP: G = (SiLU(u W_1) * u W_3) W_2
    experts: s = sigmoid(u W_r) over all experts (float32); T = the top_k
      of s (the expert bias is zeros: ``assumed``);
      w_e = scale * s_e / (sum_T s + 1e-20);
      G = sum_{e in T, held} w_e (SiLU(u W_g,e) * u W_u,e) W_d,e
    loss = mean CE(N_f(x)_i E^T, t_{i+1}), E the embedding table
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

# The parent of the PR that brought this builder has no such module: a cell
# of this builder then fails here, when the driver loads the builder.
import torchft_tpu.models.short_conv  # noqa: F401

CONV, FULL = "conv", "full_attention"

# What --rehearse shrinks the sizes to. Never a cell; never a device number.
# Every expert is selected (top 4 of 4) and half are held, as in
# ``afmoe_decoder.py`` and for its reason.
REHEARSE = dict(hidden_size=128, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=256,
                moe_intermediate_size=64, num_experts=4,
                num_experts_per_tok=4, num_experts_held=2, vocab_size=512)
REHEARSE_SEQ = 64

CONTROLS: Dict[str, Dict[str, str]] = {
    # the step below the bfloat16 that matmul inputs are stated in (the
    # router's own product stays float32, as stated)
    "fp8_matmul": {"matmul": "float8_e4m3/forward"},
    # the convolution reading the current token only (a switch: see above)
    "drop_taps": {"drop_taps": "bfloat16/forward"},
}
PROBES: Dict[str, Dict[str, str]] = {
    "stated_bf16": {"matmul": "bfloat16/forward", "residual": "bfloat16"},
    "bf16_router": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                    "router": "bfloat16/forward"},
    "bf16_islands": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                     "norm": "bfloat16", "softmax": "bfloat16",
                     "logits": "bfloat16"},
}


# ------------------------------------------------------------- the sizes

def _w(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    layers = [int(i) for i in cfg["published_layers"]]
    if len(layers) != int(cfg["num_hidden_layers"]):
        raise ValueError(f"published_layers names {len(layers)} layers, "
                         f"num_hidden_layers is {cfg['num_hidden_layers']}")
    kinds = [cfg["layer_types"][i] for i in layers]
    if set(kinds) - {CONV, FULL}:
        raise ValueError(f"layer kinds {sorted(set(kinds))}: conv and "
                         "full_attention are written here")
    dense = [i < int(cfg["num_dense_layers"]) for i in layers]
    if dense != sorted(dense, reverse=True):
        raise ValueError("the dense layers must lead")
    first = int(cfg.get("first_expert_held", 0))
    held = int(cfg["num_experts_held"])
    if first + held > int(cfg["num_experts"]):
        raise ValueError("experts held beyond num_experts")
    if cfg["conv_bias"]:
        raise ValueError("a convolution without bias is written here")
    E, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    if E % H:
        raise ValueError(f"hidden {E} over {H} heads")
    return dict(E=E, H=H, Hkv=int(cfg["num_key_value_heads"]), D=E // H,
                Kc=int(cfg["conv_L_cache"]),
                F=int(cfg["intermediate_size"]),
                Fm=int(cfg["moe_intermediate_size"]),
                V=int(cfg["vocab_size"]), L=len(layers),
                Ne=int(cfg["num_experts"]), K=int(cfg["num_experts_per_tok"]),
                first=first, held=held, kinds=kinds, dense=dense,
                scale=float(cfg["routed_scaling_factor"]),
                route_norm=bool(cfg["norm_topk_prob"]),
                eps=float(cfg["norm_eps"]),
                theta=float(cfg["rope_theta"]))


# ----------------------------------------------------- the program's model

def _make_model(cfg: Mapping[str, Any], seq: int, interpret: bool,
                dtype: Any = jnp.bfloat16, remat: bool = False) -> Any:
    """The program's model at the configuration's sizes: ``Transformer``
    with conv and full-attention layers, the flash kernel at a head of
    ``hidden / heads`` (key/value heads shared through its index maps) under
    head norms and rotary, the routed expert layer over its share with no
    shared expert, and no ``lm_head``: the head reads the table. No
    per-layer remat, as in ``mamba2_moe_decoder.py`` and for its reasons.
    ``dtype`` and ``remat`` are the tests'."""
    from torchft_tpu.models import Transformer
    from torchft_tpu.models.transformer import TransformerConfig
    from torchft_tpu.ops import flash_attention

    w = _w(cfg)
    attention = functools.partial(flash_attention, interpret=interpret)
    attention.supports_gqa = True
    tcfg = TransformerConfig(
        vocab_size=w["V"], num_layers=w["L"], embed_dim=w["E"],
        num_heads=w["H"], num_kv_heads=w["Hkv"], hidden_dim=w["F"],
        max_seq_len=seq, rope_theta=w["theta"], rms_norm_eps=w["eps"],
        attention_fn=attention, remat=remat, dtype=dtype,
        layer_types=tuple(w["kinds"]), linear_conv_kernel=w["Kc"],
        qk_norm=True, tie_embeddings=True,
        moe_experts=w["Ne"], moe_top_k=w["K"], moe_dispatch="routed",
        moe_dim=w["Fm"], moe_held=(w["first"], w["held"]),
        moe_shared_dim=0, moe_score="sigmoid",
        moe_route_norm=w["route_norm"], moe_route_scale=w["scale"],
        moe_dense_layers=sum(w["dense"]), moe_interpret=interpret)
    return Transformer(tcfg)


def make_loss_fn(cfg: Mapping[str, Any], seq: int, interpret: bool,
                 **model_kw: Any) -> Callable:
    """The program's loss: the model above and the chunked loss over the
    table."""
    from torchft_tpu.models import chunked_causal_lm_loss, head_kernel

    model = _make_model(cfg, seq, interpret, **model_kw)

    def loss_fn(params, batch):
        hidden = model.apply(params, batch["tokens"], return_hidden=True)
        return chunked_causal_lm_loss(hidden, head_kernel(params),
                                      batch["tokens"])

    return loss_fn


def program_selections(cfg: Mapping[str, Any], seq: int, interpret: bool
                       ) -> Callable:
    """``(params, tokens) -> [experts [T, K] of each expert layer]``: what
    the program's routers select (``benchmarks/route_flips.py``)."""
    model = _make_model(cfg, seq, interpret)
    layers = [i for i, dense in enumerate(_w(cfg)["dense"]) if not dense]

    def selections(params, tokens):
        _, state = model.apply(params, tokens, return_hidden=True,
                               mutable=["intermediates"])
        return [state["intermediates"][f"layer_{i}"]["moe"]["experts"][0]
                for i in layers]

    return selections


# ------------------------------------------------------------- the shapes

def param_shapes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    """The parameter tree's shapes (all float32), named as the program's
    ``Transformer`` names them. One-dimensional leaves are norm gains (made
    as ones), the others normal(0, initializer_range): the convolution's
    ``[taps, channels]`` among them. No ``lm_head`` (tied) and no expert
    bias (the configuration's ``assumed``)."""
    w = _w(cfg)
    E, H, Hkv, D = w["E"], w["H"], w["Hkv"], w["D"]
    conv = {"in_proj": {"kernel": (E, 3 * E)}, "conv": (w["Kc"], E),
            "out_proj": {"kernel": (E, E)}}
    attn = {"q": {"kernel": (E, H, D)}, "k": {"kernel": (E, Hkv, D)},
            "v": {"kernel": (E, Hkv, D)}, "q_norm": {"scale": (D,)},
            "k_norm": {"scale": (D,)}, "o": {"kernel": (H * D, E)}}
    mlp = {"gate": {"kernel": (E, w["F"])}, "up": {"kernel": (E, w["F"])},
           "down": {"kernel": (w["F"], E)}}
    moe: Dict[str, Any] = {"router": {"kernel": (E, w["Ne"])}}
    if w["held"]:
        moe.update(wi_gate=(w["held"], E, w["Fm"]),
                   wi_up=(w["held"], E, w["Fm"]),
                   wo=(w["held"], w["Fm"], E))
    tree: Dict[str, Any] = {"embed": {"embedding": (w["V"], E)},
                            "final_norm": {"scale": (E,)}}
    for i, (kind, dense) in enumerate(zip(w["kinds"], w["dense"])):
        tree[f"layer_{i}"] = {
            "attn": conv if kind == CONV else attn,
            "attn_norm": {"scale": (E,)}, "mlp_norm": {"scale": (E,)},
            **({"mlp": mlp} if dense else {"moe": moe})}
    return {"params": tree}


# ---------------------------------------------------- the plain reference

def _same(x):
    return x


def _rms_norm(x, scale, eps, r):
    x = r(x)
    mean_sq = r(jnp.mean(r(x * x), axis=-1, keepdims=True))
    return r(r(x * r(jax.lax.rsqrt(mean_sq + eps))) * scale)


def _rope(x, theta):
    """x: [B, S, H, D]; rotate the pairs (i, i + D/2) by
    position * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v, mm, soft):
    """Causal softmax attention, q [B,S,H,D], k/v [B,S,Hkv,D], one query
    head at a time (with its group's key/value head), so that the [S, S]
    scores of an 8192-token sequence stay 256 MiB."""
    B, S, H, D = q.shape
    g = H // k.shape[2]
    qh = q.transpose(2, 0, 1, 3)
    kh = jnp.repeat(k.transpose(2, 0, 1, 3), g, axis=0)
    vh = jnp.repeat(v.transpose(2, 0, 1, 3), g, axis=0)
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    @jax.checkpoint
    def one(args):
        q1, k1, v1 = args
        s = soft(jnp.einsum("bqd,bkd->bqk", mm(q1), mm(k1)) * (D ** -0.5))
        p = soft(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1))
        return jnp.einsum("bqk,bkd->bqd", mm(p), mm(v1))

    out = jax.lax.map(one, (qh, kh, vh))
    return out.transpose(1, 2, 0, 3).reshape(B, S, H * D)


def _attention_mixer(u, a, w, r):
    mm, nrm = r.get("matmul", _same), r.get("norm", _same)
    q = jnp.einsum("bse,ehd->bshd", mm(u), mm(a["q"]["kernel"]))
    k = jnp.einsum("bse,ehd->bshd", mm(u), mm(a["k"]["kernel"]))
    v = jnp.einsum("bse,ehd->bshd", mm(u), mm(a["v"]["kernel"]))
    q = _rope(_rms_norm(q, a["q_norm"]["scale"], w["eps"], nrm), w["theta"])
    k = _rope(_rms_norm(k, a["k_norm"]["scale"], w["eps"], nrm), w["theta"])
    o = _attention(q, k, v, mm, r.get("softmax", _same))
    return mm(o) @ mm(a["o"]["kernel"])


def _back(h, d: int):
    """``h`` [B, S, Ch] read ``d`` tokens back: row t holds ``h_{t-d}``,
    zeros where there is none."""
    if d == 0:
        return h
    return jnp.concatenate(
        [jnp.zeros_like(h[:, :d]), h[:, : h.shape[1] - d]], axis=1)


def _conv_mixer(u, a, w, r):
    """The gated short convolution, the convolution as the sum over its taps
    of the gated stream read that many tokens back."""
    mm = r.get("matmul", _same)
    E, K = w["E"], w["Kc"]
    bcz = mm(u) @ mm(a["in_proj"]["kernel"])
    b_gate, c_gate, z = bcz[..., :E], bcz[..., E: 2 * E], bcz[..., 2 * E:]
    h = b_gate * z
    taps = [K - 1] if "drop_taps" in r else range(K)
    c = sum(a["conv"][j] * _back(h, K - 1 - j) for j in taps)
    return mm(c_gate * c) @ mm(a["out_proj"]["kernel"])


def _swiglu(u, gate, up, down, mm):
    return mm(jax.nn.silu(mm(u) @ mm(gate)) * (mm(u) @ mm(up))) @ mm(down)


def reference_routing(u, router_kernel, w: Mapping[str, Any], rt=_same
                      ) -> Tuple[Any, Any]:
    """The selection: ``(weights [B,S,K], experts [B,S,K])``. The sum's
    ``eps`` is 1e-20, the program's: four sigmoids sum to about 2, where
    anything under 1e-7 is below float32's last bit."""
    s = rt(jax.nn.sigmoid(rt(u) @ rt(router_kernel)))
    top, idx = jax.lax.top_k(s, w["K"])
    if w["route_norm"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return top * w["scale"], idx


def experts_share(u, p, w: Mapping[str, Any], mm=_same, rt=_same,
                  collect=None):
    """The expert layer's part for the held experts: the obvious loop over
    them, each computing every token under a mask of the pairs routed to
    it. No shared expert."""
    weights, idx = reference_routing(u, p["router"]["kernel"], w, rt)
    if collect is not None:
        collect.append(idx.reshape(-1, idx.shape[-1]))
    m = jnp.zeros_like(u)
    one = jax.checkpoint(functools.partial(_swiglu, mm=mm))
    for e in range(w["held"]):
        w_e = jnp.sum(jnp.where(idx == w["first"] + e, weights, 0.0), axis=-1)
        m = m + w_e[..., None] * one(u, p["wi_gate"][e], p["wi_up"][e],
                                     p["wo"][e])
    return m


def _one_layer(x, lp, w, kind, dense, r, collect):
    res, nrm = r.get("residual", _same), r.get("norm", _same)
    mm = r.get("matmul", _same)
    u = _rms_norm(x, lp["attn_norm"]["scale"], w["eps"], nrm)
    mixer = _conv_mixer if kind == CONV else _attention_mixer
    x = res(x + mixer(u, lp["attn"], w, r))
    u = _rms_norm(x, lp["mlp_norm"]["scale"], w["eps"], nrm)
    if dense:
        d = lp["mlp"]
        g = _swiglu(u, d["gate"]["kernel"], d["up"]["kernel"],
                    d["down"]["kernel"], mm)
    else:
        g = experts_share(u, lp["moe"], w, mm, r.get("router", _same),
                          collect)
    return res(x + g)


def _layer(x, lp, w, kind, dense, r, collect):
    """One layer; without ``collect`` recomputed in the backward, so that
    five layers' float32 intermediates at 8192 tokens fit beside the tree
    and its gradients."""
    if collect is None:
        return jax.checkpoint(lambda x_, lp_: _one_layer(
            x_, lp_, w, kind, dense, r, None))(x, lp)
    return _one_layer(x, lp, w, kind, dense, r, collect)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _mean_nll(states, table, targets, mm, lg):
    logits = lg(mm(states) @ mm(table).T)
    logp = lg(jax.nn.log_softmax(logits, axis=-1))
    return jnp.mean(-jnp.take_along_axis(logp, targets[..., None], axis=-1))


def reference_selections(params: Any, tokens: Any, cfg: Mapping[str, Any],
                         rounding: Optional[Mapping[str, Callable]] = None
                         ) -> List[Any]:
    """``[experts [T, K] of each expert layer]`` as the reference selects
    them."""
    collect: List[Any] = []
    reference_loss(params, tokens, cfg, rounding, collect=collect)
    return collect


def reference_loss(params: Any, tokens: Any, cfg: Mapping[str, Any],
                   rounding: Optional[Mapping[str, Callable]] = None,
                   collect: Optional[List[Any]] = None) -> Any:
    """Mean next-token cross-entropy of ``tokens`` [B, S] in float32 at the
    highest matmul precision. ``rounding`` maps a site to a function put on
    every value there: ``matmul`` (the inputs of every matrix product but
    the router's), ``router`` (its inputs and scores), ``residual`` (the
    embedding and the stream after each addition), ``norm``, ``softmax``,
    ``logits``, and the switch ``drop_taps`` (the module docstring). A site
    that is not named is left in float32."""
    w = _w(cfg)
    r = dict(rounding or {})
    p = params["params"]
    table = p["embed"]["embedding"]
    with jax.default_matmul_precision("highest"):
        x = r.get("residual", _same)(table[tokens])
        for i, (kind, dense) in enumerate(zip(w["kinds"], w["dense"])):
            x = _layer(x, p[f"layer_{i}"], w, kind, dense, r, collect)
        x = _rms_norm(x, p["final_norm"]["scale"], w["eps"],
                      r.get("norm", _same))
        return _mean_nll(x[:, :-1], table, tokens[:, 1:],
                         r.get("matmul", _same), r.get("logits", _same))


# ------------------------------------------- what the algorithm needs
#
# Needed work only (``dense_gqa_decoder.py`` says what that leaves out): the
# causal triangle of the attention layers, and of the routed experts the
# expected ``top_k * held / num_experts`` a token (uniform routing, as the
# other builders count them).

def param_count(cfg: Mapping[str, Any]) -> int:
    """Parameters of the decoder as run, from the configuration alone. The
    table counts once: it is the head too."""
    w = _w(cfg)
    E, HD = w["E"], w["H"] * w["D"]
    mixer = {CONV: E * 3 * E + w["Kc"] * E + E * E,
             FULL: 2 * E * HD + 2 * E * w["Hkv"] * w["D"] + 2 * w["D"]}
    dense = 3 * E * w["F"]
    experts = E * w["Ne"] + w["held"] * 3 * E * w["Fm"]
    return (sum(mixer[kind] + 2 * E + (dense if d else experts)
                for kind, d in zip(w["kinds"], w["dense"]))
            + w["V"] * E + E)


def layer_forward_flops(cfg: Mapping[str, Any], seq: int
                        ) -> List[Dict[str, float]]:
    """Forward operations for one token, layer by layer and part by part."""
    w = _w(cfg)
    E, HD = w["E"], w["H"] * w["D"]
    out = []
    for kind, dense in zip(w["kinds"], w["dense"]):
        if kind == CONV:
            # the two gates are a multiplication a channel each
            part = {"proj": 2.0 * E * 3 * E + 2.0 * E * E,
                    "conv": 2.0 * w["Kc"] * E + 2.0 * E}
        else:
            part = {"proj": 2.0 * E * (HD + 2 * w["Hkv"] * w["D"])
                    + 2.0 * HD * E,
                    "attn": 2 * (2.0 * w["D"] * w["H"] * (seq + 1) / 2)}
        if dense:
            part["mlp"] = 3 * 2.0 * E * w["F"]
        else:
            part["router"] = 2.0 * E * w["Ne"]
            part["routed"] = (w["K"] * w["held"] / w["Ne"]) \
                * 3 * 2.0 * E * w["Fm"]
        out.append(part)
    return out


def forward_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    w = _w(cfg)
    return sum(sum(p.values()) for p in layer_forward_flops(cfg, seq)) \
        + 2.0 * w["E"] * w["V"]


def train_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    """Forward and backward: the backward of a matmul is two matmuls."""
    return 3.0 * forward_flops_per_token(cfg, seq)
