"""Builder ``smallthinker_decoder``: the SmallThinker decoder
(SmallThinker-21BA3B-Instruct): two-norm layers of grouped-query attention
(full causal without a positional turn, or a causal window with rotary, by
``sliding_window_layout`` / ``rope_layout``) and an expert layer whose
**router reads the attention's normed input** while its ReGLU experts
compute on the post-attention norm's output; no dense MLP, no shared expert;
**a share of the routed experts** is held (``num_experts_held`` from
``first_expert_held``). A configuration names this file by ``"builder"``.

The layers that run are ``published_layers`` (indices into the published
layouts). What a builder gives the harness is listed in
``dense_gqa_decoder.py``; the reference's rounding sites are that file's plus
``router`` (the router's matmul inputs and its weights). ``route_late`` is a
switch and no rounding, as ``drop_taps`` is in ``lfm2_moe_decoder.py``: the
precision given is ignored and the reference's router reads ``u``, the
experts' input, where the model's reads ``h`` (the control of that name: a
program that routed after attention would match it).

The equations, ``N`` RMSNorm (``y = w x / sqrt(mean x^2 + eps)``), layer
``i`` over the stream ``x`` [T, E]; no bias anywhere:

    h = N_in(x);  logits = h W_r  (E -> all experts, float32)
    q = h W_q (H heads x D), k, v = h W_k, h W_v (H_kv x D); where
      rope_layout[i] = 1: rotary over the whole head, pairs (i, i + D/2),
      theta rope_theta
    a = softmax(q k^T / sqrt(D) + mask_i) v: causal, and where
      sliding_window_layout[i] = 1 key j visible to query i iff
      0 <= i - j < sliding_window_size
    x1 = x + a W_o;  u = N_post(x1)
    S = the top_k largest of logits; w_e = exp(logits_e) / sum_S exp(logits)
      (the published order: the selection on raw logits, then a softmax
      over the selected; ``torchft_tpu.models.moe.route`` takes the softmax
      over all first and divides by the selection's sum, which is the same)
    m = sum_{e in S, held} w_e (relu(u W_g,e) * (u W_u,e)) W_d,e
    x <- x1 + m
    loss = mean CE(N_f(x)_i W_head, t_{i+1})
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

FULL, SLIDING = "full_attention", "sliding_attention"

# What --rehearse shrinks the sizes to. Never a cell; never a device number.
# Seven query heads on one key/value head, as the model's 28 on 4. Every
# expert is selected (top 4 of 4) and half are held, as in
# ``afmoe_decoder.py`` and for its reason. The derived names (:func:`derived`)
# shrink with their published keys.
REHEARSE = dict(hidden_size=128, num_attention_heads=7,
                num_key_value_heads=1, head_dim=32,
                moe_ffn_hidden_size=64, moe_intermediate_size=64,
                moe_num_primary_experts=4, moe_num_active_primary_experts=4,
                num_experts_held=2, vocab_size=512,
                sliding_window_size=16, sliding_window=16)
REHEARSE_SEQ = 64

CONTROLS: Dict[str, Dict[str, str]] = {
    # the step below the bfloat16 that matmul inputs are stated in (the
    # router's own product stays float32, as stated)
    "fp8_matmul": {"matmul": "float8_e4m3/forward"},
    # the router reading the experts' input (a switch: see above)
    "route_late": {"route_late": "bfloat16/forward"},
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

def derived(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    """The names the benchmark's kernel files read
    (``kernels/window_flash_attention.py``, ``kernels/grouped_matmul.py``),
    each from the published key that holds its value."""
    return {
        "layer_types": [SLIDING if s else FULL
                        for s in cfg["sliding_window_layout"]],
        "sliding_window": int(cfg["sliding_window_size"]),
        "moe_intermediate_size": int(cfg["moe_ffn_hidden_size"]),
        "num_dense_layers": 0,
    }


def _w(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    layers = [int(i) for i in cfg["published_layers"]]
    if len(layers) != int(cfg["num_hidden_layers"]):
        raise ValueError(f"published_layers names {len(layers)} layers, "
                         f"num_hidden_layers is {cfg['num_hidden_layers']}")
    named = derived(cfg)
    for key, value in named.items():
        if cfg.get(key, value) != value:
            raise ValueError(f"{key} {cfg[key]!r} is not what its published "
                             f"key gives ({value!r})")
    kinds = [named["layer_types"][i] for i in layers]
    if [k == SLIDING for k in kinds] != [bool(cfg["rope_layout"][i])
                                         for i in layers]:
        raise ValueError("windowed layers with rotary and full layers "
                         "without are written here")
    if not (cfg["moe_primary_router_apply_softmax"]
            and cfg["norm_topk_prob"]):
        raise ValueError("a softmax over the selected logits is written "
                         "here")
    if cfg["tie_word_embeddings"]:
        raise ValueError("a head of its own is written here")
    first = int(cfg.get("first_expert_held", 0))
    held = int(cfg["num_experts_held"])
    if first + held > int(cfg["moe_num_primary_experts"]):
        raise ValueError("experts held beyond moe_num_primary_experts")
    return dict(E=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
                Hkv=int(cfg["num_key_value_heads"]), D=int(cfg["head_dim"]),
                Fm=int(cfg["moe_ffn_hidden_size"]),
                V=int(cfg["vocab_size"]), L=len(layers),
                Ne=int(cfg["moe_num_primary_experts"]),
                K=int(cfg["moe_num_active_primary_experts"]),
                first=first, held=held, kinds=kinds,
                window=named["sliding_window"],
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


# ----------------------------------------------------- the program's model

def _make_model(cfg: Mapping[str, Any], seq: int, interpret: bool,
                dtype: Any = jnp.bfloat16, remat: bool = False) -> Any:
    """The program's model at the configuration's sizes: ``Transformer``
    with windowed (rotary) and full (no rotary) layers, the flash kernels at
    the stated head size (key/value heads shared through their index maps),
    and in every layer the routed expert layer over its share in the
    ``"reglu"`` form, its softmax router reading the mixer's input
    (``moe_route_input="mixer"``). No per-layer remat, as in
    ``mamba2_moe_decoder.py`` and for its reasons. ``dtype`` and ``remat``
    are the tests'. A tree without that field or that form stops here."""
    from torchft_tpu.models import Transformer
    from torchft_tpu.models.transformer import TransformerConfig
    from torchft_tpu.ops import flash_attention

    w = _w(cfg)
    attention = functools.partial(flash_attention, interpret=interpret)
    attention.supports_gqa = True
    tcfg = TransformerConfig(
        vocab_size=w["V"], num_layers=w["L"], embed_dim=w["E"],
        num_heads=w["H"], num_kv_heads=w["Hkv"], attn_head_dim=w["D"],
        hidden_dim=w["Fm"], max_seq_len=seq, rope_theta=w["theta"],
        rms_norm_eps=w["eps"], attention_fn=attention, remat=remat,
        dtype=dtype, layer_types=tuple(w["kinds"]),
        sliding_window=w["window"], rope_full_layers=False,
        moe_experts=w["Ne"], moe_top_k=w["K"], moe_dispatch="routed",
        moe_dim=w["Fm"], moe_held=(w["first"], w["held"]),
        moe_shared_dim=0, moe_score="softmax", moe_route_norm=True,
        moe_route_scale=1.0, moe_form="reglu", moe_route_input="mixer",
        moe_dense_layers=0, moe_interpret=interpret)
    return Transformer(tcfg)


def make_loss_fn(cfg: Mapping[str, Any], seq: int, interpret: bool,
                 **model_kw: Any) -> Callable:
    """The program's loss: the model above and the chunked loss over its
    head."""
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
    layers = range(_w(cfg)["L"])

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
    as ones), the others normal(0, initializer_range)."""
    w = _w(cfg)
    E, H, Hkv, D = w["E"], w["H"], w["Hkv"], w["D"]
    attn = {"q": {"kernel": (E, H, D)}, "k": {"kernel": (E, Hkv, D)},
            "v": {"kernel": (E, Hkv, D)}, "o": {"kernel": (H * D, E)}}
    moe: Dict[str, Any] = {"router": {"kernel": (E, w["Ne"])}}
    if w["held"]:
        moe.update(wi_gate=(w["held"], E, w["Fm"]),
                   wi_up=(w["held"], E, w["Fm"]),
                   wo=(w["held"], w["Fm"], E))
    tree: Dict[str, Any] = {"embed": {"embedding": (w["V"], E)},
                            "final_norm": {"scale": (E,)},
                            "lm_head": {"kernel": (E, w["V"])}}
    for i in range(w["L"]):
        tree[f"layer_{i}"] = {"attn": attn, "attn_norm": {"scale": (E,)},
                              "mlp_norm": {"scale": (E,)}, "moe": moe}
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


def _attention(q, k, v, window: Optional[int], mm, soft):
    """Softmax attention, q [B,S,H,D], k/v [B,S,Hkv,D]: key j is visible to
    query i iff 0 <= i - j and, with a window, i - j < window. One query
    head at a time (with its group's key/value head), so that the [S, S]
    scores of an 8192-token sequence stay 256 MiB."""
    B, S, H, D = q.shape
    g = H // k.shape[2]
    qh = q.transpose(2, 0, 1, 3)
    kh = jnp.repeat(k.transpose(2, 0, 1, 3), g, axis=0)
    vh = jnp.repeat(v.transpose(2, 0, 1, 3), g, axis=0)
    back = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]      # i - j
    mask = back >= 0
    if window is not None:
        mask = jnp.logical_and(mask, back < window)

    @jax.checkpoint
    def one(args):
        q1, k1, v1 = args
        s = soft(jnp.einsum("bqd,bkd->bqk", mm(q1), mm(k1)) * (D ** -0.5))
        p = soft(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1))
        return jnp.einsum("bqk,bkd->bqd", mm(p), mm(v1))

    out = jax.lax.map(one, (qh, kh, vh))
    return out.transpose(1, 2, 0, 3).reshape(B, S, H * D)


def _attention_mixer(h, a, w, kind, r):
    mm = r.get("matmul", _same)
    q = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["q"]["kernel"]))
    k = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["k"]["kernel"]))
    v = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["v"]["kernel"]))
    if kind == SLIDING:
        q, k = _rope(q, w["theta"]), _rope(k, w["theta"])
    o = _attention(q, k, v, w["window"] if kind == SLIDING else None, mm,
                   r.get("softmax", _same))
    return mm(o) @ mm(a["o"]["kernel"])


def _reglu(u, gate, up, down, mm):
    return mm(jax.nn.relu(mm(u) @ mm(gate)) * (mm(u) @ mm(up))) @ mm(down)


def reference_routing(read, router_kernel, w: Mapping[str, Any], rt=_same
                      ) -> Tuple[Any, Any]:
    """The selection from the stream the router reads: ``(weights [B,S,K],
    experts [B,S,K])``, the ``K`` largest logits and a softmax over them."""
    logits = rt(read) @ rt(router_kernel)
    top, idx = jax.lax.top_k(logits, w["K"])
    return rt(jax.nn.softmax(top, axis=-1)), idx


def experts_share(u, read, p, w: Mapping[str, Any], mm=_same, rt=_same,
                  collect=None):
    """The expert layer's part for the held experts, routed on ``read`` and
    computed on ``u``: the obvious loop over them, each computing every
    token under a mask of the pairs routed to it. No shared expert."""
    weights, idx = reference_routing(read, p["router"]["kernel"], w, rt)
    if collect is not None:
        collect.append(idx.reshape(-1, idx.shape[-1]))
    m = jnp.zeros_like(u)
    one = jax.checkpoint(functools.partial(_reglu, mm=mm))
    for e in range(w["held"]):
        w_e = jnp.sum(jnp.where(idx == w["first"] + e, weights, 0.0), axis=-1)
        m = m + w_e[..., None] * one(u, p["wi_gate"][e], p["wi_up"][e],
                                     p["wo"][e])
    return m


def _one_layer(x, lp, w, kind, r, collect):
    res, nrm = r.get("residual", _same), r.get("norm", _same)
    h = _rms_norm(x, lp["attn_norm"]["scale"], w["eps"], nrm)
    x = res(x + _attention_mixer(h, lp["attn"], w, kind, r))
    u = _rms_norm(x, lp["mlp_norm"]["scale"], w["eps"], nrm)
    m = experts_share(u, u if "route_late" in r else h, lp["moe"], w,
                      r.get("matmul", _same), r.get("router", _same),
                      collect)
    return res(x + m)


def _layer(x, lp, w, kind, r, collect):
    """One layer; without ``collect`` recomputed in the backward, so that
    four layers' float32 intermediates at 8192 tokens fit beside the tree
    and its gradients."""
    if collect is None:
        return jax.checkpoint(lambda x_, lp_: _one_layer(
            x_, lp_, w, kind, r, None))(x, lp)
    return _one_layer(x, lp, w, kind, r, collect)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _mean_nll(states, head, targets, mm, lg):
    logits = lg(mm(states) @ mm(head))
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
    the router's), ``router`` (its inputs and weights), ``residual`` (the
    embedding and the stream after each addition), ``norm``, ``softmax``,
    ``logits``, and the switch ``route_late`` (the module docstring). A site
    that is not named is left in float32."""
    w = _w(cfg)
    r = dict(rounding or {})
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = r.get("residual", _same)(p["embed"]["embedding"][tokens])
        for i, kind in enumerate(w["kinds"]):
            x = _layer(x, p[f"layer_{i}"], w, kind, r, collect)
        x = _rms_norm(x, p["final_norm"]["scale"], w["eps"],
                      r.get("norm", _same))
        return _mean_nll(x[:, :-1], p["lm_head"]["kernel"], tokens[:, 1:],
                         r.get("matmul", _same), r.get("logits", _same))


# ------------------------------------------- what the algorithm needs
#
# Needed work only (``dense_gqa_decoder.py`` says what that leaves out): each
# attention layer's visible part of the score matrix (the causal triangle,
# or the band under the window), and of the routed experts the expected
# ``top_k * held / num_experts`` a token (uniform routing, as the other
# builders count them). A ReGLU is three products of a SwiGLU's sizes.

def param_count(cfg: Mapping[str, Any]) -> int:
    """Parameters of the decoder as run, from the configuration alone."""
    w = _w(cfg)
    E, HD = w["E"], w["H"] * w["D"]
    attn = 2 * E * HD + 2 * E * w["Hkv"] * w["D"]
    experts = E * w["Ne"] + w["held"] * 3 * E * w["Fm"]
    return w["L"] * (attn + 2 * E + experts) + 2 * w["V"] * E + E


def visible_keys_per_query(seq: int, window: Optional[int]) -> float:
    """Keys a query sees, averaged over a ``seq``-token sequence."""
    if window is None or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def layer_forward_flops(cfg: Mapping[str, Any], seq: int
                        ) -> List[Dict[str, float]]:
    """Forward operations for one token, layer by layer and part by part."""
    w = _w(cfg)
    E, HD = w["E"], w["H"] * w["D"]
    out = []
    for kind in w["kinds"]:
        keys = visible_keys_per_query(
            seq, w["window"] if kind == SLIDING else None)
        out.append({
            "proj": 2.0 * E * (HD + 2 * w["Hkv"] * w["D"]) + 2.0 * HD * E,
            "attn": 2 * (2.0 * w["D"] * w["H"] * keys),
            "router": 2.0 * E * w["Ne"],
            "routed": (w["K"] * w["held"] / w["Ne"]) * 3 * 2.0 * E * w["Fm"],
        })
    return out


def forward_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    w = _w(cfg)
    return sum(sum(p.values()) for p in layer_forward_flops(cfg, seq)) \
        + 2.0 * w["E"] * w["V"]


def train_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    """Forward and backward: the backward of a matmul is two matmuls."""
    return 3.0 * forward_flops_per_token(cfg, seq)
