"""Builder ``afmoe_decoder``: the ``afmoe`` decoder (Trinity-Mini): layers of
two attention kinds (a causal window with rotary, full causal without),
RMSNorm of queries and keys per head, a sigmoid gate on the attention
output, four norms a layer, the embedding times sqrt(hidden); leading dense
SwiGLU layers, then expert layers: sigmoid scores over all experts, the top
k normalised and scaled, one shared expert, and **a share of the routed
experts** (``num_experts_held`` from ``first_expert_held``): the layer routes
over all ``num_experts`` and computes its own experts' part. A configuration
names this file by ``"builder"``.

The layers that run are ``published_layers`` (indices into the published
``layer_types``; one below ``num_dense_layers`` is dense). What a builder
gives the harness is listed in ``dense_gqa_decoder.py``; the reference's
rounding sites are that file's plus ``router`` (the router's matmul inputs
and its scores).

The layer equations, with ``N`` RMSNorm (eps 1e-5) and ``h = N_in(x)``:

    x0 = E[tokens] * sqrt(hidden)
    q, k, v, g = h W_q, h W_k, h W_v, h W_g;  q, k <- N_head(q), N_head(k)
    rotary(q, k) only in a sliding layer;  key j visible to query i iff
    0 <= i - j (and i - j < window in a sliding layer)
    a = (attn * sigmoid(g)) W_o;  x <- x + N_post_attn(a)
    u = N_pre_mlp(x);  m = MLP(u);  x <- x + N_post_mlp(m)
    expert MLP: s = sigmoid(u W_r); S = the top_k of s;
    w_e = route_scale * s_e / (sum_{S} s + 1e-20);
    m = SwiGLU_shared(u) + sum_{e in S, held} w_e SwiGLU_e(u)
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

SLIDING, FULL = "sliding_attention", "full_attention"

# What --rehearse shrinks the sizes to. Never a cell; never a device number.
# Every expert is selected (top 4 of 4) and half are held: the rehearsal
# checks the plumbing of routing, grouping and the share, not the selection,
# which bfloat16 flips at such sizes often enough to swamp a gradient
# distance over 64 tokens (tests/test_afmoe_model.py compares the selection
# in float32).
REHEARSE = dict(hidden_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, intermediate_size=256,
                moe_intermediate_size=64, num_experts=4,
                num_experts_per_tok=4, num_experts_held=2, vocab_size=512,
                sliding_window=16)
REHEARSE_SEQ = 64

CONTROLS: Dict[str, Dict[str, str]] = {
    # the step below the bfloat16 that matmul inputs are stated in (the
    # router's own product stays float32, as stated; its input has passed
    # through the rounded products above it)
    "fp8_matmul": {"matmul": "float8_e4m3/forward"},
}
PROBES: Dict[str, Dict[str, str]] = {
    "stated_bf16": {"matmul": "bfloat16/forward", "residual": "bfloat16"},
    # the router lowered too: its inputs and scores in bfloat16
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
    dense = [i < int(cfg["num_dense_layers"]) for i in layers]
    if dense != sorted(dense, reverse=True):
        raise ValueError("the dense layers must lead")
    first = int(cfg.get("first_expert_held", 0))
    held = int(cfg["num_experts_held"])
    if first + held > int(cfg["num_experts"]):
        raise ValueError("experts held beyond num_experts")
    return dict(E=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
                Hkv=int(cfg["num_key_value_heads"]), D=int(cfg["head_dim"]),
                F=int(cfg["intermediate_size"]),
                Fm=int(cfg["moe_intermediate_size"]),
                Fs=int(cfg["moe_intermediate_size"])
                * int(cfg["num_shared_experts"]),
                V=int(cfg["vocab_size"]), L=len(layers),
                Ne=int(cfg["num_experts"]), K=int(cfg["num_experts_per_tok"]),
                first=first, held=held, kinds=kinds, dense=dense,
                window=int(cfg["sliding_window"]),
                scale=float(cfg["route_scale"]),
                route_norm=bool(cfg["route_norm"]),
                mup=bool(cfg["mup_enabled"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


# ----------------------------------------------------- the program's model

def _make_model(cfg: Mapping[str, Any], seq: int, interpret: bool,
                dtype: Any = jnp.bfloat16, remat: bool = True) -> Any:
    """The program's model at the configuration's sizes: ``Transformer``
    with the afmoe options, the flash kernel (windowed in the sliding
    layers), the routed expert layer over its share and per-layer remat.
    ``dtype`` and ``remat`` are the tests': float32 compute compares with
    the reference to float32's own error."""
    from torchft_tpu.models import Transformer
    from torchft_tpu.models.transformer import TransformerConfig

    w = _w(cfg)
    if abs(w["eps"] - 1e-5) > 1e-12:
        raise ValueError("the program's RMSNorm has eps 1e-5 fixed; this "
                         f"configuration states {cfg['rms_norm_eps']}")
    if cfg.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("afmoe scores are sigmoid")
    from torchft_tpu.ops import flash_attention

    attention = functools.partial(flash_attention, interpret=interpret)
    # The kernel shares key/value heads through its index maps; without the
    # mark Attention would repeat them eight times over at these widths.
    attention.supports_gqa = True
    tcfg = TransformerConfig(
        vocab_size=w["V"], num_layers=w["L"], embed_dim=w["E"],
        num_heads=w["H"], num_kv_heads=w["Hkv"], hidden_dim=w["F"],
        max_seq_len=seq, rope_theta=w["theta"],
        attention_fn=attention, remat=remat, dtype=dtype,
        moe_experts=w["Ne"], moe_top_k=w["K"], moe_dispatch="routed",
        moe_dim=w["Fm"], moe_held=(w["first"], w["held"]),
        moe_shared_dim=w["Fs"], moe_score="sigmoid",
        moe_route_norm=w["route_norm"], moe_route_scale=w["scale"],
        moe_dense_layers=sum(w["dense"]), moe_interpret=interpret,
        layer_types=tuple(w["kinds"]), sliding_window=w["window"],
        rope_full_layers=False, attn_head_dim=w["D"], qk_norm=True,
        attn_gate=True, sandwich_norm=True, embed_scale=w["mup"])
    return Transformer(tcfg)


def make_loss_fn(cfg: Mapping[str, Any], seq: int, interpret: bool,
                 **model_kw: Any) -> Callable:
    """The program's loss: the model above and the chunked loss."""
    from torchft_tpu.models import chunked_causal_lm_loss

    model = _make_model(cfg, seq, interpret, **model_kw)

    def loss_fn(params, batch):
        hidden = model.apply(params, batch["tokens"], return_hidden=True)
        return chunked_causal_lm_loss(
            hidden, params["params"]["lm_head"]["kernel"], batch["tokens"])

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
    ``Transformer`` names them. One-dimensional leaves are norm scales (made
    as ones), the others matrices (made normal(0, initializer_range)). The
    expert bias is not in the tree (the configuration's ``assumed``)."""
    w = _w(cfg)
    E, H, Hkv, D = w["E"], w["H"], w["Hkv"], w["D"]

    def swiglu(width):
        return {"gate": {"kernel": (E, width)}, "up": {"kernel": (E, width)},
                "down": {"kernel": (width, E)}}

    attn = {"q": {"kernel": (E, H, D)}, "k": {"kernel": (E, Hkv, D)},
            "v": {"kernel": (E, Hkv, D)}, "q_norm": {"scale": (D,)},
            "k_norm": {"scale": (D,)}, "gate": {"kernel": (E, H * D)},
            "o": {"kernel": (H * D, E)}}
    norms = {n: {"scale": (E,)} for n in
             ("attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm")}
    moe: Dict[str, Any] = {"router": {"kernel": (E, w["Ne"])}}
    if w["held"]:
        moe.update(wi_gate=(w["held"], E, w["Fm"]),
                   wi_up=(w["held"], E, w["Fm"]),
                   wo=(w["held"], w["Fm"], E))
    if w["Fs"]:
        moe["shared"] = swiglu(w["Fs"])
    tree: Dict[str, Any] = {"embed": {"embedding": (w["V"], E)},
                            "final_norm": {"scale": (E,)},
                            "lm_head": {"kernel": (E, w["V"])}}
    for i, dense in enumerate(w["dense"]):
        tree[f"layer_{i}"] = {"attn": attn, **norms,
                              **({"mlp": swiglu(w["F"])} if dense
                                 else {"moe": moe})}
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


def _attention(q, k, v, window: Optional[int], mm, soft):
    """Masked softmax attention, q [B,S,H,D], k/v [B,S,Hkv,D]: key j is
    visible to query i iff 0 <= i - j and, with a window, i - j < window.
    One query head at a time (with its group's key/value head), so that the
    [S, S] scores of an 8192-token sequence stay 256 MiB."""
    B, S, H, D = q.shape
    g = H // k.shape[2]
    qh = q.transpose(2, 0, 1, 3)                              # [H,B,S,D]
    kh = jnp.repeat(k.transpose(2, 0, 1, 3), g, axis=0)       # [H,B,S,D]
    vh = jnp.repeat(v.transpose(2, 0, 1, 3), g, axis=0)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    mask = i >= j
    if window is not None:
        mask = jnp.logical_and(mask, i - j < window)

    @jax.checkpoint
    def one(args):
        q1, k1, v1 = args
        s = soft(jnp.einsum("bqd,bkd->bqk", mm(q1), mm(k1)) * (D ** -0.5))
        p = soft(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1))
        return jnp.einsum("bqk,bkd->bqd", mm(p), mm(v1))

    out = jax.lax.map(one, (qh, kh, vh))                      # [H,B,S,D]
    return out.transpose(1, 2, 0, 3).reshape(B, S, H * D)


def _swiglu(u, gate, up, down, mm):
    return mm(jax.nn.silu(mm(u) @ mm(gate)) * (mm(u) @ mm(up))) @ mm(down)


def reference_routing(u, router_kernel, w: Mapping[str, Any], rt=_same
                      ) -> Tuple[Any, Any]:
    """The selection: ``(weights [B,S,K], experts [B,S,K])``."""
    s = rt(jax.nn.sigmoid(rt(u) @ rt(router_kernel)))
    top, idx = jax.lax.top_k(s, w["K"])
    if w["route_norm"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return top * w["scale"], idx


def _experts(u, p, w: Mapping[str, Any], mm, rt, collect=None):
    """The expert layer's part for the held experts: the obvious loop over
    them, each computing every token under a mask of the pairs routed to
    it, and the shared expert."""
    weights, idx = reference_routing(u, p["router"]["kernel"], w, rt)
    if collect is not None:
        collect.append(idx.reshape(-1, idx.shape[-1]))
    m = jnp.zeros_like(u)
    if w["Fs"]:
        sh = p["shared"]
        m = _swiglu(u, sh["gate"]["kernel"], sh["up"]["kernel"],
                    sh["down"]["kernel"], mm)
    one = jax.checkpoint(functools.partial(_swiglu, mm=mm))
    for e in range(w["held"]):
        w_e = jnp.sum(jnp.where(idx == w["first"] + e, weights, 0.0), axis=-1)
        m = m + w_e[..., None] * one(u, p["wi_gate"][e], p["wi_up"][e],
                                     p["wo"][e])
    return m


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
    the router's), ``router`` (the router's inputs and scores), ``residual``
    (the embedding and the stream after each addition), ``norm``,
    ``softmax``, ``logits``. A site that is not named is left in float32."""
    w = _w(cfg)
    r = dict(rounding or {})
    mm, res = r.get("matmul", _same), r.get("residual", _same)
    nrm, soft, lg = (r.get("norm", _same), r.get("softmax", _same),
                     r.get("logits", _same))
    rt = r.get("router", _same)
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][tokens]
        if w["mup"]:
            x = x * math.sqrt(w["E"])
        x = res(x)
        for i in range(w["L"]):
            lp = p[f"layer_{i}"]
            sliding = w["kinds"][i] == SLIDING
            h = _rms_norm(x, lp["attn_norm"]["scale"], w["eps"], nrm)
            a = lp["attn"]
            q = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["q"]["kernel"]))
            k = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["k"]["kernel"]))
            v = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["v"]["kernel"]))
            g = mm(h) @ mm(a["gate"]["kernel"])
            q = _rms_norm(q, a["q_norm"]["scale"], w["eps"], nrm)
            k = _rms_norm(k, a["k_norm"]["scale"], w["eps"], nrm)
            if sliding:
                q, k = _rope(q, w["theta"]), _rope(k, w["theta"])
            o = _attention(q, k, v, w["window"] if sliding else None,
                           mm, soft)
            o = mm(o * jax.nn.sigmoid(g)) @ mm(a["o"]["kernel"])
            x = res(x + _rms_norm(o, lp["post_attn_norm"]["scale"],
                                  w["eps"], nrm))
            u = _rms_norm(x, lp["mlp_norm"]["scale"], w["eps"], nrm)
            if w["dense"][i]:
                d = lp["mlp"]
                m = _swiglu(u, d["gate"]["kernel"], d["up"]["kernel"],
                            d["down"]["kernel"], mm)
            else:
                m = _experts(u, lp["moe"], w, mm, rt, collect)
            x = res(x + _rms_norm(m, lp["post_mlp_norm"]["scale"],
                                  w["eps"], nrm))
        x = _rms_norm(x, p["final_norm"]["scale"], w["eps"], nrm)
        logits = lg(mm(x[:, :-1]) @ mm(p["lm_head"]["kernel"]))
        logp = lg(jax.nn.log_softmax(logits, axis=-1))
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return jnp.mean(nll)


# ------------------------------------------- what the algorithm needs
#
# Needed work only (``dense_gqa_decoder.py`` says what that leaves out): the
# visible part of each layer's attention by its kind, and of the routed
# experts the expected ``top_k * held / num_experts`` a token.

def param_count(cfg: Mapping[str, Any]) -> int:
    """Parameters of the decoder as run, from the configuration alone."""
    w = _w(cfg)
    E, HD = w["E"], w["H"] * w["D"]
    attn = E * HD * 2 + 2 * E * w["Hkv"] * w["D"] + HD * E + 2 * w["D"]
    norms = 4 * E
    dense = 3 * E * w["F"]
    experts = E * w["Ne"] + 3 * E * w["Fs"] + w["held"] * 3 * E * w["Fm"]
    n_dense = sum(w["dense"])
    return (w["L"] * (attn + norms) + n_dense * dense
            + (w["L"] - n_dense) * experts + 2 * w["V"] * E + E)


def visible_keys_per_query(seq: int, window: Optional[int]) -> float:
    """Mean over a ``seq``-token sequence's queries of the keys each sees."""
    if window is None or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def layer_forward_flops(cfg: Mapping[str, Any], seq: int
                        ) -> List[Dict[str, float]]:
    """Forward operations for one token, layer by layer and part by part."""
    w = _w(cfg)
    E, HD = w["E"], w["H"] * w["D"]
    out = []
    for kind, dense in zip(w["kinds"], w["dense"]):
        seen = visible_keys_per_query(
            seq, w["window"] if kind == SLIDING else None)
        part = {"proj": 2.0 * E * (2 * HD + 2 * w["Hkv"] * w["D"])
                + 2.0 * HD * E,
                "attn": 2 * (2.0 * w["D"] * w["H"] * seen)}
        if dense:
            part["mlp"] = 3 * 2.0 * E * w["F"]
        else:
            part["router"] = 2.0 * E * w["Ne"]
            part["shared"] = 3 * 2.0 * E * w["Fs"]
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
