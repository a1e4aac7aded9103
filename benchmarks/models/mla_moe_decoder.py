"""Builder ``mla_moe_decoder``: the DeepSeek-V3 block at JoyAI-LLM-Flash's
numbers: latent attention (low-rank query and key/value projections with an
RMSNorm inside each, a rotary key of one head shared by every query head,
rotary on part of the head in adjacent pairs, a value head narrower than the
query/key head), pre-norm with two norms a layer, leading dense SwiGLU
layers, then expert layers (sigmoid scores over all experts, the top k
normalised and scaled, one shared expert, **a share of the routed experts**
held here), an untied head, and one multi-token-prediction module that reads
the trunk's embedding and head: two losses a step. A configuration names
this file by ``"builder"``.

``published_layers`` lists the published indices that run: those below the
published depth are the trunk (one below ``num_dense_layers`` is dense), the
index equal to it is the prediction module. What a builder gives the harness
is listed in ``dense_gqa_decoder.py``; the reference's rounding sites are
that file's plus ``router``.

The equations, ``N`` RMSNorm (eps from the configuration), ``h = N_in(x)``:

    c_q = N(h W_qa);  q = c_q W_qb -> H heads of [q_nope | q_rope]
    [c_kv | k_r] = h W_kva;  c_kv <- N(c_kv)
    [k_nope | v] per head = c_kv W_kvb
    rotary, pairs (2i, 2i+1) by pos * theta^(-2i/d_rope), on q_rope and on
    k_r (ONE head for all H);  q = [q_nope | q_rope], k = [k_nope | k_r]
    a = softmax_causal(q k^T / sqrt(d_nope + d_rope)) v W_o;  x <- x + a
    x <- x + MLP(N_post(x))
    expert MLP: s = sigmoid(u W_r); S = the top_k of s;
    w_e = scale * s_e / (sum_S s + 1e-20);
    m = SwiGLU_shared(u) + sum_{e in S, held} w_e SwiGLU_e(u)
    g = N_f(x);  L_main = mean CE(g_i W_head, t_{i+1})
    z_i = [N_e(E[t_{i+1}]) ; N_h(g_i)] W_eh;  z <- Layer(z);
    L_mtp = mean over i <= T-2 of CE(N_m(z_i) W_head, t_{i+2})
    L = L_main + mtp_loss_weight * L_mtp
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

# What --rehearse shrinks the sizes to. Never a cell; never a device number.
# Every expert is selected (top 4 of 4) and half are held, as in
# ``afmoe_decoder.py`` and for its reason: bfloat16 flips a real selection
# often enough at such sizes to swamp a gradient distance over 64 tokens
# (tests/test_mla_model.py compares the selection in float32).
REHEARSE = dict(hidden_size=128, num_attention_heads=4,
                num_key_value_heads=4, q_lora_rank=96, kv_lora_rank=64,
                qk_nope_head_dim=32, qk_rope_head_dim=16, qk_head_dim=48,
                v_head_dim=32, intermediate_size=256,
                moe_intermediate_size=64, n_routed_experts=4,
                num_experts_per_tok=4, num_experts_held=2, vocab_size=512)
REHEARSE_SEQ = 64

CONTROLS: Dict[str, Dict[str, str]] = {
    # the step below the bfloat16 that matmul inputs are stated in (the
    # router's own product stays float32, as stated)
    "fp8_matmul": {"matmul": "float8_e4m3/forward"},
}
PROBES: Dict[str, Dict[str, str]] = {
    "stated_bf16": {"matmul": "bfloat16/forward", "residual": "bfloat16"},
    # the router lowered too: its inputs and scores in bfloat16
    "bf16_router": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                    "router": "bfloat16/forward"},
    # the float32 islands lowered: every norm (the two latent ones inside
    # the projections among them), softmax, logits and log-softmax
    "bf16_islands": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                     "norm": "bfloat16", "softmax": "bfloat16",
                     "logits": "bfloat16"},
}

HEADS_AT_ONCE = 4   # the reference's attention: [4, S, S] scores at a time


# ------------------------------------------------------------- the sizes

def _w(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    depth = int(cfg["published"]["num_hidden_layers"])
    listed = [int(i) for i in cfg["published_layers"]]
    layers = [i for i in listed if i < depth]
    modules = [i for i in listed if i >= depth]
    if len(layers) != int(cfg["num_hidden_layers"]):
        raise ValueError(f"published_layers names {len(layers)} trunk "
                         f"layers, num_hidden_layers is "
                         f"{cfg['num_hidden_layers']}")
    if modules != [depth] or int(cfg["num_nextn_predict_layers"]) != 1:
        raise ValueError("this builder runs exactly one prediction module, "
                         f"published index {depth}")
    dense = [i < int(cfg["num_dense_layers"]) for i in layers]
    if dense != sorted(dense, reverse=True):
        raise ValueError("the dense layers must lead")
    if int(cfg["num_dense_layers"]) != int(cfg["first_k_dense_replace"]):
        raise ValueError("num_dense_layers restates first_k_dense_replace")
    first = int(cfg.get("first_expert_held", 0))
    held = int(cfg["num_experts_held"])
    if first + held > int(cfg["n_routed_experts"]):
        raise ValueError("experts held beyond n_routed_experts")
    if int(cfg["n_group"]) != 1 or int(cfg["topk_group"]) != 1:
        raise ValueError("grouped selection is not written here")
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    if nope + rope != int(cfg["qk_head_dim"]):
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim")
    return dict(E=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
                Rq=int(cfg["q_lora_rank"]), Rkv=int(cfg["kv_lora_rank"]),
                nope=nope, rope=rope, Dv=int(cfg["v_head_dim"]),
                F=int(cfg["intermediate_size"]),
                Fm=int(cfg["moe_intermediate_size"]),
                Fs=int(cfg["moe_intermediate_size"])
                * int(cfg["n_shared_experts"]),
                V=int(cfg["vocab_size"]), L=len(layers),
                Ne=int(cfg["n_routed_experts"]),
                K=int(cfg["num_experts_per_tok"]),
                first=first, held=held, dense=dense,
                scale=float(cfg["routed_scaling_factor"]),
                route_norm=bool(cfg["norm_topk_prob"]),
                interleave=bool(cfg["rope_interleave"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                lam=float(cfg["mtp_loss_weight"]))


# ----------------------------------------------------- the program's model

def _make_model(cfg: Mapping[str, Any], seq: int, interpret: bool,
                dtype: Any = jnp.bfloat16, remat: bool = True) -> Any:
    """The program's model at the configuration's sizes: ``Transformer``
    with latent attention through the flash kernel (two head sizes), the
    routed expert layer over its share, the prediction module and per-layer
    remat. ``dtype`` and ``remat`` are the tests'."""
    from torchft_tpu.models import Transformer
    from torchft_tpu.models.transformer import TransformerConfig
    from torchft_tpu.ops import flash_attention

    w = _w(cfg)
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("this block's scores are sigmoid")
    if cfg.get("rope_scaling") is not None or cfg.get("attention_bias"):
        raise ValueError("no rotary scaling and no bias are written here")
    tcfg = TransformerConfig(
        vocab_size=w["V"], num_layers=w["L"], embed_dim=w["E"],
        num_heads=w["H"], hidden_dim=w["F"], max_seq_len=seq,
        rope_theta=w["theta"], rms_norm_eps=w["eps"],
        attention_fn=functools.partial(flash_attention, interpret=interpret),
        remat=remat, dtype=dtype,
        q_lora_rank=w["Rq"], kv_lora_rank=w["Rkv"],
        qk_nope_head_dim=w["nope"], qk_rope_head_dim=w["rope"],
        v_head_dim=w["Dv"], rope_interleave=w["interleave"],
        moe_experts=w["Ne"], moe_top_k=w["K"], moe_dispatch="routed",
        moe_dim=w["Fm"], moe_held=(w["first"], w["held"]),
        moe_shared_dim=w["Fs"], moe_score="sigmoid",
        moe_route_norm=w["route_norm"], moe_route_scale=w["scale"],
        moe_dense_layers=sum(w["dense"]), moe_interpret=interpret,
        mtp_layers=1)
    return Transformer(tcfg)


def make_loss_fn(cfg: Mapping[str, Any], seq: int, interpret: bool,
                 **model_kw: Any) -> Callable:
    """The program's loss: the model above, the chunked loss twice over the
    one head kernel, ``L_main + lambda L_mtp``."""
    from torchft_tpu.models import mtp_causal_lm_loss

    model = _make_model(cfg, seq, interpret, **model_kw)
    lam = _w(cfg)["lam"]

    def loss_fn(params, batch):
        return mtp_causal_lm_loss(model, params, batch["tokens"], lam)

    return loss_fn


def _expert_layer_names(w: Mapping[str, Any]) -> List[Tuple[str, ...]]:
    """Where each expert layer's ``moe`` sits in a tree: the trunk's, then
    the module's."""
    return [(f"layer_{i}",) for i, dense in enumerate(w["dense"])
            if not dense] + [("mtp", "block")]


def program_selections(cfg: Mapping[str, Any], seq: int, interpret: bool
                       ) -> Callable:
    """``(params, tokens) -> [experts [T, K] of each expert layer]``: what
    the program's routers select (``benchmarks/route_flips.py``); of the
    module's layer the ``T - 1`` positions a sequence that have a next
    token, as the reference runs it."""
    model = _make_model(cfg, seq, interpret)
    names = _expert_layer_names(_w(cfg))

    def selections(params, tokens):
        _, state = model.apply(params, tokens, return_mtp=True,
                               mutable=["intermediates"])
        out = []
        for path in names:
            node = state["intermediates"]
            for key in path:
                node = node[key]
            picked = node["moe"]["experts"][0]
            if path[0] == "mtp":
                k = picked.shape[-1]
                picked = picked.reshape(*tokens.shape, k)[:, :-1].reshape(
                    -1, k)
            out.append(picked)
        return out

    return selections


# ------------------------------------------------------------- the shapes

def param_shapes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    """The parameter tree's shapes (all float32), named as the program's
    ``Transformer`` names them. One-dimensional leaves are norm scales (made
    as ones), the others matrices (made normal(0, initializer_range)). The
    score-correction bias is not in the tree (the configuration's
    ``assumed``)."""
    w = _w(cfg)
    E, H = w["E"], w["H"]

    def swiglu(width):
        return {"gate": {"kernel": (E, width)}, "up": {"kernel": (E, width)},
                "down": {"kernel": (width, E)}}

    attn = {"q_a": {"kernel": (E, w["Rq"])}, "q_norm": {"scale": (w["Rq"],)},
            "q_b": {"kernel": (w["Rq"], H, w["nope"] + w["rope"])},
            "kv_a": {"kernel": (E, w["Rkv"] + w["rope"])},
            "kv_norm": {"scale": (w["Rkv"],)},
            "expand": {"kv_b": {"kernel": (w["Rkv"], H,
                                           w["nope"] + w["Dv"])}},
            "o": {"kernel": (H * w["Dv"], E)}}
    norms = {n: {"scale": (E,)} for n in ("attn_norm", "mlp_norm")}
    moe: Dict[str, Any] = {"router": {"kernel": (E, w["Ne"])}}
    if w["held"]:
        moe.update(wi_gate=(w["held"], E, w["Fm"]),
                   wi_up=(w["held"], E, w["Fm"]),
                   wo=(w["held"], w["Fm"], E))
    if w["Fs"]:
        moe["shared"] = swiglu(w["Fs"])
    expert_layer = {"attn": attn, **norms, "moe": moe}
    tree: Dict[str, Any] = {"embed": {"embedding": (w["V"], E)},
                            "final_norm": {"scale": (E,)},
                            "lm_head": {"kernel": (E, w["V"])}}
    for i, dense in enumerate(w["dense"]):
        tree[f"layer_{i}"] = ({"attn": attn, **norms,
                               "mlp": swiglu(w["F"])} if dense
                              else expert_layer)
    tree["mtp"] = {"embed_norm": {"scale": (E,)},
                   "hidden_norm": {"scale": (E,)},
                   "proj": {"kernel": (2 * E, E)}, "block": expert_layer,
                   "final_norm": {"scale": (E,)}}
    return {"params": tree}


# ---------------------------------------------------- the plain reference

def _same(x):
    return x


def _rms_norm(x, scale, eps, r):
    x = r(x)
    mean_sq = r(jnp.mean(r(x * x), axis=-1, keepdims=True))
    return r(r(x * r(jax.lax.rsqrt(mean_sq + eps))) * scale)


def _cos_sin(x, theta):
    """cos and sin of position * theta^(-2i/D), i < D/2, for x [B, S, H, D]:
    each [1, S, 1, D/2]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]


def _rope_pairs(x, theta):
    """x: [B, S, H, D]; the pair (x[2i], x[2i+1]) is a complex number turned
    by the angle position * theta^(-2i/D)."""
    d = x.shape[-1]
    cos, sin = _cos_sin(x, theta)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _rope_halves(x, theta):
    """The half-split convention (``rope_interleave`` false): pairs
    (i, i + D/2)."""
    d = x.shape[-1]
    cos, sin = _cos_sin(x, theta)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v, mm, soft):
    """Causal masked softmax attention, q/k [B,S,H,Dqk], v [B,S,H,Dv], a
    few heads at a time so that the [S, S] scores of an 8192-token sequence
    stay 1 GiB."""
    B, S, H, D = q.shape
    n = min(HEADS_AT_ONCE, H)
    assert H % n == 0

    def heads(x):                              # [H/n, n, B, S, d]
        return x.transpose(2, 0, 1, 3).reshape(H // n, n, B, S, x.shape[-1])

    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    @jax.checkpoint
    def some(args):
        q1, k1, v1 = args
        s = soft(jnp.einsum("hbqd,hbkd->hbqk", mm(q1), mm(k1)) * (D ** -0.5))
        p = soft(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1))
        return jnp.einsum("hbqk,hbkd->hbqd", mm(p), mm(v1))

    out = jax.lax.map(some, (heads(q), heads(k), heads(v)))
    out = out.reshape(H, B, S, v.shape[-1])
    return out.transpose(1, 2, 0, 3).reshape(B, S, H * v.shape[-1])


def _latent_attention(h, a, w: Mapping[str, Any], mm, nrm, soft):
    nope, Rkv = w["nope"], w["Rkv"]
    rope = _rope_pairs if w["interleave"] else _rope_halves
    c_q = _rms_norm(mm(h) @ mm(a["q_a"]["kernel"]), a["q_norm"]["scale"],
                    w["eps"], nrm)
    q = jnp.einsum("bsr,rhd->bshd", mm(c_q), mm(a["q_b"]["kernel"]))
    kv = mm(h) @ mm(a["kv_a"]["kernel"])
    c_kv = _rms_norm(kv[..., :Rkv], a["kv_norm"]["scale"], w["eps"], nrm)
    k_r = rope(kv[..., None, Rkv:], w["theta"])               # [B,S,1,rope]
    kv = jnp.einsum("bsr,rhd->bshd", mm(c_kv),
                    mm(a["expand"]["kv_b"]["kernel"]))
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], w["theta"])],
                        axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, q.shape[:3] + (w["rope"],))],
        axis=-1)
    o = _attention(q, k, kv[..., nope:], mm, soft)
    return mm(o) @ mm(a["o"]["kernel"])


def _swiglu(u, gate, up, down, mm):
    return mm(jax.nn.silu(mm(u) @ mm(gate)) * (mm(u) @ mm(up))) @ mm(down)


def reference_routing(u, router_kernel, w: Mapping[str, Any], rt=_same
                      ) -> Tuple[Any, Any]:
    """The selection: ``(weights [B,S,K], experts [B,S,K])``. The score
    correction bias is zero (``assumed``), so the selection is the top k of
    the scores themselves."""
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


def _layer(x, lp, w, dense, r, collect):
    """One layer. Without ``collect`` it is recomputed in the backward
    (``jax.checkpoint``): six layers' float32 intermediates at 8192 tokens
    do not fit the chip beside the tree and its gradients (15.81 GiB by the
    chip's compiler; PERF.md, PR 33)."""
    if collect is None:
        return jax.checkpoint(
            lambda x_, lp_: _one_layer(x_, lp_, w, dense, r, None))(x, lp)
    return _one_layer(x, lp, w, dense, r, collect)


def _one_layer(x, lp, w, dense, r, collect):
    mm, res = r.get("matmul", _same), r.get("residual", _same)
    nrm, soft = r.get("norm", _same), r.get("softmax", _same)
    h = _rms_norm(x, lp["attn_norm"]["scale"], w["eps"], nrm)
    x = res(x + _latent_attention(h, lp["attn"], w, mm, nrm, soft))
    u = _rms_norm(x, lp["mlp_norm"]["scale"], w["eps"], nrm)
    if dense:
        d = lp["mlp"]
        m = _swiglu(u, d["gate"]["kernel"], d["up"]["kernel"],
                    d["down"]["kernel"], mm)
    else:
        m = _experts(u, lp["moe"], w, mm, r.get("router", _same), collect)
    return res(x + m)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _mean_nll(states, head, targets, mm, lg):
    logits = lg(mm(states) @ mm(head))
    logp = lg(jax.nn.log_softmax(logits, axis=-1))
    return jnp.mean(-jnp.take_along_axis(logp, targets[..., None], axis=-1))


def reference_selections(params: Any, tokens: Any, cfg: Mapping[str, Any],
                         rounding: Optional[Mapping[str, Callable]] = None
                         ) -> List[Any]:
    """``[experts [T, K] of each expert layer]`` as the reference selects
    them: the trunk's expert layers, then the module's (``T - 1`` positions
    a sequence)."""
    collect: List[Any] = []
    reference_losses(params, tokens, cfg, rounding, collect=collect)
    return collect


def reference_losses(params: Any, tokens: Any, cfg: Mapping[str, Any],
                     rounding: Optional[Mapping[str, Callable]] = None,
                     collect: Optional[List[Any]] = None) -> Tuple[Any, Any]:
    """``(L_main, L_mtp)`` of ``tokens`` [B, T] in float32 at the highest
    matmul precision. The module runs over the ``T - 1`` positions that
    have a next token, and its loss over the ``T - 2`` that have one after
    that."""
    w = _w(cfg)
    r = dict(rounding or {})
    mm, res = r.get("matmul", _same), r.get("residual", _same)
    nrm, lg = r.get("norm", _same), r.get("logits", _same)
    p = params["params"]
    head = p["lm_head"]["kernel"]
    with jax.default_matmul_precision("highest"):
        table = p["embed"]["embedding"]
        x = res(table[tokens])
        for i in range(w["L"]):
            x = _layer(x, p[f"layer_{i}"], w, w["dense"][i], r, collect)
        g = _rms_norm(x, p["final_norm"]["scale"], w["eps"], nrm)
        main = _mean_nll(g[:, :-1], head, tokens[:, 1:], mm, lg)
        m = p["mtp"]
        z = jnp.concatenate(
            [_rms_norm(res(table[tokens[:, 1:]]), m["embed_norm"]["scale"],
                       w["eps"], nrm),
             _rms_norm(g[:, :-1], m["hidden_norm"]["scale"], w["eps"], nrm)],
            axis=-1)
        z = res(mm(z) @ mm(m["proj"]["kernel"]))
        z = _layer(z, m["block"], w, False, r, collect)
        z = _rms_norm(z, m["final_norm"]["scale"], w["eps"], nrm)
        mtp = _mean_nll(z[:, :-1], head, tokens[:, 2:], mm, lg)
        return main, mtp


def reference_loss(params: Any, tokens: Any, cfg: Mapping[str, Any],
                   rounding: Optional[Mapping[str, Callable]] = None,
                   collect: Optional[List[Any]] = None) -> Any:
    """``L_main + lambda L_mtp``. ``rounding`` maps a site to a function put
    on every value there: ``matmul`` (the inputs of every matrix product but
    the router's), ``router`` (the router's inputs and scores), ``residual``
    (the embeddings and the stream after each addition), ``norm``,
    ``softmax``, ``logits``. A site that is not named is left in float32."""
    main, mtp = reference_losses(params, tokens, cfg, rounding, collect)
    return main + _w(cfg)["lam"] * mtp


# ------------------------------------------- what the algorithm needs
#
# Needed work only (``dense_gqa_decoder.py`` says what that leaves out): the
# causal triangle of each layer's attention at its two head sizes, of the
# routed experts the expected ``top_k * held / n_routed_experts`` a token,
# and the head twice (one product a loss).

def param_count(cfg: Mapping[str, Any]) -> int:
    """Parameters of the decoder as run, from the configuration alone."""
    w = _w(cfg)
    E, H = w["E"], w["H"]
    attn = (E * w["Rq"] + w["Rq"] * H * (w["nope"] + w["rope"])
            + E * (w["Rkv"] + w["rope"]) + w["Rkv"] * H * (w["nope"] + w["Dv"])
            + H * w["Dv"] * E + w["Rq"] + w["Rkv"])
    norms = 2 * E
    dense = 3 * E * w["F"]
    experts = E * w["Ne"] + 3 * E * w["Fs"] + w["held"] * 3 * E * w["Fm"]
    n_dense = sum(w["dense"])
    block = attn + norms + experts
    module = 2 * E * E + block + 3 * E
    return (n_dense * (attn + norms + dense) + (w["L"] - n_dense) * block
            + module + 2 * w["V"] * E + E)


def layer_forward_flops(cfg: Mapping[str, Any], seq: int
                        ) -> List[Dict[str, float]]:
    """Forward operations for one token, layer by layer and part by part:
    the trunk's layers, then the module (whose ``eh_proj`` is its own)."""
    w = _w(cfg)
    E, H = w["E"], w["H"]
    seen = (seq + 1) / 2
    attn = {"proj": 2.0 * (E * w["Rq"] + w["Rq"] * H * (w["nope"] + w["rope"])
                           + E * (w["Rkv"] + w["rope"])
                           + w["Rkv"] * H * (w["nope"] + w["Dv"])
                           + H * w["Dv"] * E),
            "attn": 2.0 * (w["nope"] + w["rope"] + w["Dv"]) * H * seen}
    expert = {"router": 2.0 * E * w["Ne"], "shared": 3 * 2.0 * E * w["Fs"],
              "routed": (w["K"] * w["held"] / w["Ne"]) * 3 * 2.0 * E * w["Fm"]}
    out = [{**attn, **({"mlp": 3 * 2.0 * E * w["F"]} if dense else expert)}
           for dense in w["dense"]]
    out.append({**attn, **expert, "eh_proj": 2.0 * 2 * E * E})
    return out


def forward_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    w = _w(cfg)
    return sum(sum(p.values()) for p in layer_forward_flops(cfg, seq)) \
        + 2 * 2.0 * w["E"] * w["V"]


def train_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    """Forward and backward: the backward of a matmul is two matmuls."""
    return 3.0 * forward_flops_per_token(cfg, seq)
