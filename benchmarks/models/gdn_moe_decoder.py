"""Builder ``gdn_moe_decoder``: the Qwen3-Next block: Gated DeltaNet
linear-attention layers (fused projections, a causal depthwise convolution,
L2-normalised queries and keys, a per-head decay and write strength, the
gated delta rule along the sequence, a gated per-head RMSNorm) three to one
among gated full-attention layers (head size 256, 16 query heads on 2
key/value heads, RMSNorm of queries and keys per head, rotary on the leading
quarter of the head); every layer's MLP an expert layer (softmax scores over
all experts, the top k normalised, a shared expert behind a sigmoid gate,
**a share of the routed experts** held here), pre-norm with two norms a
layer, an untied head. A configuration names this file by ``"builder"``.

``published_layers`` lists the published indices that run; layer ``i`` is
full attention iff ``(i + 1) % full_attention_interval == 0``. What a builder
gives the harness is listed in ``dense_gqa_decoder.py``; the reference's
rounding sites are that file's plus ``router``, ``state`` (the recurrence's
state as it passes from one block of ``STATE_BLOCK`` tokens to the next:
where the program carries it from chunk to chunk) and ``decay`` (the log
decay ``g`` and ``alpha = exp(g)``). ``drop_carry`` is a switch and no
rounding: ``harness/reference.py`` can name a site only with a precision, so
the precision given is ignored and the state entering each block is zero
(the control ``no_carry``: a scan that loses what it carries).

The equations, ``N`` RMSNorm (eps from the configuration), ``u = N_1(x)``:

    linear layer: [q | k | v | z] = u W_qkvz;  [b | a] = u W_ba
      [q | k | v] <- SiLU(conv): y_t = sum_{j<4} c_j * x_{t-3+j}, zeros
      left of the sequence;  q, k <- q / sqrt(sum q^2 + 1e-6) per head,
      q <- q d_k^-1/2, each key head serving H / H_k value heads
      beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias + shift)
      S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T
      o_t = S_t^T q_t;  o <- N_head(o) * w_n * SiLU(z);  out = [o] W_out
    full layer: q, gate, k, v = u W_q, u W_g, u W_k, u W_v;  q, k <-
      N_head(q), N_head(k);  rotary (halves paired) on the first
      ``partial_rotary_factor * head_dim`` dims;  a = softmax_causal(q k^T /
      sqrt(head_dim)) v;  out = (a * sigmoid(gate)) W_o
    x <- x + mixer;  u' = N_2(x)
    expert MLP: p = softmax(u' W_r);  S = the top_k of p;
      w_e = p_e / (sum_S p + 1e-20);
      m = sigmoid(u' w_s) SwiGLU_shared(u')
          + sum_{e in S, held} w_e SwiGLU_e(u')
    x <- x + m;  loss = mean CE(N_f(x)_i W_head, t_{i+1})

``dt_bias_shift`` (the configuration's; added to the seeded ``dt_bias`` leaf
by the program's loss function and by the reference alike) is there because
the harness seeds every one-dimensional leaf at one, under which a state
forgets everything within a few tokens and a scan that dropped its carry
could not be told from a sound one.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

LINEAR, FULL = "linear_attention", "full_attention"

# What --rehearse shrinks the sizes to. Never a cell; never a device number.
# Every expert is selected (top 4 of 4) and half are held, as in
# ``afmoe_decoder.py`` and for its reason; two chunks of the scan, so that a
# state is carried.
REHEARSE = dict(hidden_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, linear_num_key_heads=2,
                linear_key_head_dim=16, linear_num_value_heads=4,
                linear_value_head_dim=16, moe_intermediate_size=64,
                shared_expert_intermediate_size=64, num_experts=4,
                num_experts_per_tok=4, num_experts_held=2, vocab_size=512)
REHEARSE_SEQ = 128

CONTROLS: Dict[str, Dict[str, str]] = {
    # the step below the bfloat16 that matmul inputs are stated in (the
    # router's own product stays float32, as stated)
    "fp8_matmul": {"matmul": "float8_e4m3/forward"},
    # the state zeroed at every boundary of 64 tokens (a switch: see above)
    "no_carry": {"drop_carry": "bfloat16/forward"},
}
PROBES: Dict[str, Dict[str, str]] = {
    "stated_bf16": {"matmul": "bfloat16/forward", "residual": "bfloat16"},
    "bf16_router": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                    "router": "bfloat16/forward"},
    "bf16_islands": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                     "norm": "bfloat16", "softmax": "bfloat16",
                     "logits": "bfloat16"},
    # what the program keeps in float32 along the sequence, lowered
    "bf16_state": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                   "state": "bfloat16"},
    "bf16_decay": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                   "decay": "bfloat16"},
}

STATE_BLOCK = 64   # tokens of the reference's recurrence between two
#                    checkpoints of its state (and ``no_carry``'s boundary)


# ------------------------------------------------------------- the sizes

def _w(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    layers = [int(i) for i in cfg["published_layers"]]
    if len(layers) != int(cfg["num_hidden_layers"]):
        raise ValueError(f"published_layers names {len(layers)} layers, "
                         f"num_hidden_layers is {cfg['num_hidden_layers']}")
    every = int(cfg["full_attention_interval"])
    kinds = [FULL if (i + 1) % every == 0 else LINEAR for i in layers]
    if (int(cfg["num_dense_layers"]) or cfg["mlp_only_layers"]
            or int(cfg["decoder_sparse_step"]) != 1):
        raise ValueError("every layer's MLP is an expert layer here")
    first = int(cfg.get("first_expert_held", 0))
    held = int(cfg["num_experts_held"])
    if first + held > int(cfg["num_experts"]):
        raise ValueError("experts held beyond num_experts")
    D = int(cfg["head_dim"])
    rot = int(round(D * float(cfg["partial_rotary_factor"])))
    if rot % 2 or not 0 < rot <= D:
        raise ValueError(f"rotary over {rot} of {D} dims")
    return dict(E=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
                Hkv=int(cfg["num_key_value_heads"]), D=D, rot=rot,
                Hk=int(cfg["linear_num_key_heads"]),
                Dk=int(cfg["linear_key_head_dim"]),
                Hv=int(cfg["linear_num_value_heads"]),
                Dv=int(cfg["linear_value_head_dim"]),
                Kc=int(cfg["linear_conv_kernel_dim"]),
                Fm=int(cfg["moe_intermediate_size"]),
                Fs=int(cfg["shared_expert_intermediate_size"]),
                V=int(cfg["vocab_size"]), L=len(layers),
                Ne=int(cfg["num_experts"]), K=int(cfg["num_experts_per_tok"]),
                first=first, held=held, kinds=kinds,
                route_norm=bool(cfg["norm_topk_prob"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                shift=float(cfg["dt_bias_shift"]))


# ----------------------------------------------------- the program's model

def _make_model(cfg: Mapping[str, Any], seq: int, interpret: bool,
                dtype: Any = jnp.bfloat16, remat: bool = True) -> Any:
    """The program's model at the configuration's sizes: ``Transformer``
    with linear-attention and full layers, the flash kernel at the full
    layers' head size (key/value heads shared through its index maps), the
    routed expert layer over its share and per-layer remat. ``dtype`` and
    ``remat`` are the tests'."""
    from torchft_tpu.models import Transformer
    from torchft_tpu.models.transformer import TransformerConfig
    from torchft_tpu.ops import flash_attention

    w = _w(cfg)
    if cfg.get("rope_scaling") is not None or cfg.get("use_sliding_window"):
        raise ValueError("no rotary scaling and no window are written here")
    attention = functools.partial(flash_attention, interpret=interpret)
    attention.supports_gqa = True
    tcfg = TransformerConfig(
        vocab_size=w["V"], num_layers=w["L"], embed_dim=w["E"],
        num_heads=w["H"], num_kv_heads=w["Hkv"], max_seq_len=seq,
        rope_theta=w["theta"], rms_norm_eps=w["eps"],
        attention_fn=attention, remat=remat, dtype=dtype,
        layer_types=tuple(w["kinds"]), attn_head_dim=w["D"],
        rotary_dim=w["rot"], qk_norm=True, attn_gate=True,
        linear_key_heads=w["Hk"], linear_key_dim=w["Dk"],
        linear_value_heads=w["Hv"], linear_value_dim=w["Dv"],
        linear_conv_kernel=w["Kc"],
        moe_experts=w["Ne"], moe_top_k=w["K"], moe_dispatch="routed",
        moe_dim=w["Fm"], moe_held=(w["first"], w["held"]),
        moe_shared_dim=w["Fs"], moe_shared_gate=True, moe_score="softmax",
        moe_route_norm=w["route_norm"], moe_route_scale=1.0,
        moe_interpret=interpret)
    return Transformer(tcfg)


def _shifted(params: Any, w: Mapping[str, Any]) -> Any:
    """``params`` with ``dt_bias_shift`` added to every linear layer's
    ``dt_bias``: the same as seeding the leaf that much lower, and the
    leaf's gradient is unchanged."""
    p = dict(params["params"])
    for i, kind in enumerate(w["kinds"]):
        if kind == LINEAR:
            layer = p[f"layer_{i}"]
            attn = layer["attn"]
            p[f"layer_{i}"] = {**layer, "attn": {
                **attn, "dt_bias": attn["dt_bias"] + w["shift"]}}
    return {**params, "params": p}


def make_loss_fn(cfg: Mapping[str, Any], seq: int, interpret: bool,
                 **model_kw: Any) -> Callable:
    """The program's loss: the model above and the chunked loss."""
    from torchft_tpu.models import chunked_causal_lm_loss

    model = _make_model(cfg, seq, interpret, **model_kw)
    w = _w(cfg)

    def loss_fn(params, batch):
        hidden = model.apply(_shifted(params, w), batch["tokens"],
                             return_hidden=True)
        return chunked_causal_lm_loss(
            hidden, params["params"]["lm_head"]["kernel"], batch["tokens"])

    return loss_fn


def program_selections(cfg: Mapping[str, Any], seq: int, interpret: bool
                       ) -> Callable:
    """``(params, tokens) -> [experts [T, K] of each expert layer]``: what
    the program's routers select (``benchmarks/route_flips.py``)."""
    model = _make_model(cfg, seq, interpret)
    w = _w(cfg)

    def selections(params, tokens):
        _, state = model.apply(_shifted(params, w), tokens,
                               return_hidden=True, mutable=["intermediates"])
        return [state["intermediates"][f"layer_{i}"]["moe"]["experts"][0]
                for i in range(w["L"])]

    return selections


# ------------------------------------------------------------- the shapes

def param_shapes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    """The parameter tree's shapes (all float32), named as the program's
    ``Transformer`` names them. One-dimensional leaves (norm gains, ``A_log``,
    ``dt_bias``) are made as ones, the others normal(0, initializer_range):
    the convolution's ``[kernel, channels]`` and the shared expert's gate
    ``[hidden, 1]`` among them."""
    w = _w(cfg)
    E = w["E"]
    conv_ch = 2 * w["Hk"] * w["Dk"] + w["Hv"] * w["Dv"]

    def swiglu(width):
        return {"gate": {"kernel": (E, width)}, "up": {"kernel": (E, width)},
                "down": {"kernel": (width, E)}}

    linear = {"in_qkvz": {"kernel": (E, conv_ch + w["Hv"] * w["Dv"])},
              "in_ba": {"kernel": (E, 2 * w["Hv"])},
              "conv": (w["Kc"], conv_ch), "A_log": (w["Hv"],),
              "dt_bias": (w["Hv"],), "norm": (w["Dv"],),
              "out": {"kernel": (w["Hv"] * w["Dv"], E)}}
    H, Hkv, D = w["H"], w["Hkv"], w["D"]
    full = {"q": {"kernel": (E, H, D)}, "k": {"kernel": (E, Hkv, D)},
            "v": {"kernel": (E, Hkv, D)}, "q_norm": {"scale": (D,)},
            "k_norm": {"scale": (D,)}, "gate": {"kernel": (E, H * D)},
            "o": {"kernel": (H * D, E)}}
    moe: Dict[str, Any] = {"router": {"kernel": (E, w["Ne"])},
                           "shared": swiglu(w["Fs"]),
                           "shared_gate": {"kernel": (E, 1)}}
    if w["held"]:
        moe.update(wi_gate=(w["held"], E, w["Fm"]),
                   wi_up=(w["held"], E, w["Fm"]),
                   wo=(w["held"], w["Fm"], E))
    tree: Dict[str, Any] = {"embed": {"embedding": (w["V"], E)},
                            "final_norm": {"scale": (E,)},
                            "lm_head": {"kernel": (E, w["V"])}}
    for i, kind in enumerate(w["kinds"]):
        tree[f"layer_{i}"] = {"attn": linear if kind == LINEAR else full,
                              "attn_norm": {"scale": (E,)},
                              "mlp_norm": {"scale": (E,)}, "moe": moe}
    return {"params": tree}


# ---------------------------------------------------- the plain reference

def _same(x):
    return x


def _rms_norm(x, scale, eps, r):
    x = r(x)
    mean_sq = r(jnp.mean(r(x * x), axis=-1, keepdims=True))
    return r(r(x * r(jax.lax.rsqrt(mean_sq + eps))) * scale)


def _rope_leading(x, theta, rot):
    """x: [B, S, H, D]; the first ``rot`` dims turn, pairs (i, i + rot/2)
    by position * theta^(-2i/rot); the other ``D - rot`` pass."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


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


def _full_mixer(h, a, w, r):
    mm, nrm = r.get("matmul", _same), r.get("norm", _same)
    q = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["q"]["kernel"]))
    k = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["k"]["kernel"]))
    v = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["v"]["kernel"]))
    gate = mm(h) @ mm(a["gate"]["kernel"])
    q = _rope_leading(_rms_norm(q, a["q_norm"]["scale"], w["eps"], nrm),
                      w["theta"], w["rot"])
    k = _rope_leading(_rms_norm(k, a["k_norm"]["scale"], w["eps"], nrm),
                      w["theta"], w["rot"])
    o = _attention(q, k, v, mm, r.get("softmax", _same))
    return mm(o * jax.nn.sigmoid(gate)) @ mm(a["o"]["kernel"])


def _causal_conv(x, c):
    """x [B, S, Ch], c [K, Ch]: y_t = sum_j c_j * x_{t-K+1+j}, zeros left
    of the sequence."""
    K, S = c.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(c[j] * xp[:, j: j + S] for j in range(K))


def delta_rule_by_token(q, k, v, alpha, beta, st=_same, carry: bool = True):
    """The gated delta rule token by token: q, k [B,S,H,Dk], v [B,S,H,Dv],
    alpha = exp(g), beta [B,S,H] -> [B,S,H,Dv]. The recurrence runs in blocks
    of ``STATE_BLOCK`` tokens only so that its backward fits: the state entering
    a block is kept, the states inside are recomputed. ``st`` is put on the
    state as it passes from block to block; ``carry=False`` passes zeros."""
    B, S, H, Dv = v.shape
    n = -(-S // STATE_BLOCK)
    pad = n * STATE_BLOCK - S

    def blocks(x, fill=0.0):   # [B, S, H, ...] -> [n, STATE_BLOCK, B, H, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
                    constant_values=fill)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(n, STATE_BLOCK, *x.shape[1:])

    def token(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        state = a_t[..., None, None] * state
        seen = jnp.einsum("bhd,bhde->bhe", k_t, state)
        state = state + k_t[..., :, None] \
            * (b_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.einsum("bhd,bhde->bhe", q_t, state)

    @jax.checkpoint
    def block(state, xs):
        state, out = jax.lax.scan(token, state, xs)
        state = st(state)
        return (state if carry else jnp.zeros_like(state)), out

    # a padded token keeps the state (alpha 1) and writes nothing (beta 0)
    _, out = jax.lax.scan(
        block, jnp.zeros((B, H, k.shape[-1], Dv), jnp.float32),
        (blocks(q), blocks(k), blocks(v), blocks(alpha, 1.0), blocks(beta)))
    out = out.reshape(n * STATE_BLOCK, B, H, Dv)[:S]
    return jnp.moveaxis(out, 0, 1)


def _linear_mixer(h, a, w, r):
    mm, nrm = r.get("matmul", _same), r.get("norm", _same)
    dc = r.get("decay", _same)
    B, S, _ = h.shape
    Hk, Dk, Hv, Dv = w["Hk"], w["Dk"], w["Hv"], w["Dv"]
    conv_ch = 2 * Hk * Dk + Hv * Dv
    qkvz = mm(h) @ mm(a["in_qkvz"]["kernel"])
    ba = mm(h) @ mm(a["in_ba"]["kernel"])
    qkv = jax.nn.silu(_causal_conv(qkvz[..., :conv_ch], a["conv"]))
    z = qkvz[..., conv_ch:].reshape(B, S, Hv, Dv)

    def unit(y):
        y = y.reshape(B, S, Hk, Dk)
        y = y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(y, Hv // Hk, axis=2)

    q = unit(qkv[..., : Hk * Dk]) * Dk ** -0.5
    k = unit(qkv[..., Hk * Dk: 2 * Hk * Dk])
    v = qkv[..., 2 * Hk * Dk:].reshape(B, S, Hv, Dv)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = dc(-jnp.exp(a["A_log"])
           * jax.nn.softplus(ba[..., Hv:] + a["dt_bias"] + w["shift"]))
    o = delta_rule_by_token(mm(q), mm(k), mm(v), dc(jnp.exp(g)), beta,
                            r.get("state", _same), "drop_carry" not in r)
    o = _rms_norm(o, a["norm"], w["eps"], nrm) * jax.nn.silu(z)
    return mm(o.reshape(B, S, Hv * Dv)) @ mm(a["out"]["kernel"])


def _swiglu(u, gate, up, down, mm):
    return mm(jax.nn.silu(mm(u) @ mm(gate)) * (mm(u) @ mm(up))) @ mm(down)


def reference_routing(u, router_kernel, w: Mapping[str, Any], rt=_same
                      ) -> Tuple[Any, Any]:
    """The selection: ``(weights [B,S,K], experts [B,S,K])``."""
    p = rt(jax.nn.softmax(rt(u) @ rt(router_kernel), axis=-1))
    top, idx = jax.lax.top_k(p, w["K"])
    if w["route_norm"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return top, idx


def _experts(u, p, w: Mapping[str, Any], mm, rt, collect=None):
    """The expert layer's part for the held experts: the obvious loop over
    them, each computing every token under a mask of the pairs routed to
    it, and the shared expert behind its gate."""
    weights, idx = reference_routing(u, p["router"]["kernel"], w, rt)
    if collect is not None:
        collect.append(idx.reshape(-1, idx.shape[-1]))
    sh = p["shared"]
    m = jax.nn.sigmoid(mm(u) @ mm(p["shared_gate"]["kernel"])) * _swiglu(
        u, sh["gate"]["kernel"], sh["up"]["kernel"], sh["down"]["kernel"], mm)
    one = jax.checkpoint(functools.partial(_swiglu, mm=mm))
    for e in range(w["held"]):
        w_e = jnp.sum(jnp.where(idx == w["first"] + e, weights, 0.0), axis=-1)
        m = m + w_e[..., None] * one(u, p["wi_gate"][e], p["wi_up"][e],
                                     p["wo"][e])
    return m


def _one_layer(x, lp, w, kind, r, collect):
    mm, res = r.get("matmul", _same), r.get("residual", _same)
    nrm = r.get("norm", _same)
    h = _rms_norm(x, lp["attn_norm"]["scale"], w["eps"], nrm)
    mixer = _linear_mixer if kind == LINEAR else _full_mixer
    x = res(x + mixer(h, lp["attn"], w, r))
    u = _rms_norm(x, lp["mlp_norm"]["scale"], w["eps"], nrm)
    return res(x + _experts(u, lp["moe"], w, mm, r.get("router", _same),
                            collect))


def _layer(x, lp, w, kind, r, collect):
    """One layer; without ``collect`` recomputed in the backward, so that
    four layers' float32 intermediates at 8192 tokens fit beside the tree
    and its gradients."""
    if collect is None:
        return jax.checkpoint(
            lambda x_, lp_: _one_layer(x_, lp_, w, kind, r, None))(x, lp)
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
    the router's, the delta rule's q, k and v among them), ``router`` (its
    inputs and scores), ``residual``, ``norm``, ``softmax``, ``logits``,
    ``state``, ``decay``, and the switch ``drop_carry`` (the module
    docstring). A site that is not named is left in float32."""
    w = _w(cfg)
    r = dict(rounding or {})
    mm, res = r.get("matmul", _same), r.get("residual", _same)
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        x = res(p["embed"]["embedding"][tokens])
        for i, kind in enumerate(w["kinds"]):
            x = _layer(x, p[f"layer_{i}"], w, kind, r, collect)
        x = _rms_norm(x, p["final_norm"]["scale"], w["eps"],
                      r.get("norm", _same))
        return _mean_nll(x[:, :-1], p["lm_head"]["kernel"], tokens[:, 1:],
                         mm, r.get("logits", _same))


# ------------------------------------------- what the algorithm needs
#
# Needed work only (``dense_gqa_decoder.py`` says what that leaves out): the
# causal triangle of a full layer's attention, of the routed experts the
# expected ``top_k * held / num_experts`` a token, and of a linear layer's
# rule the products of the chunked form at the program's chunk (the
# triangular ones counted by their triangle): the token-by-token form does
# fewer operations and is no way to run a matrix unit.

GDN_CHUNK = 64   # the chunk the operations are counted at (ops/gated_delta.py)


def param_count(cfg: Mapping[str, Any]) -> int:
    """Parameters of the decoder as run, from the configuration alone."""
    w = _w(cfg)
    E = w["E"]
    conv_ch = 2 * w["Hk"] * w["Dk"] + w["Hv"] * w["Dv"]
    vd = w["Hv"] * w["Dv"]
    linear = (E * (conv_ch + vd) + E * 2 * w["Hv"] + w["Kc"] * conv_ch
              + 2 * w["Hv"] + w["Dv"] + vd * E)
    HD = w["H"] * w["D"]
    full = 2 * E * HD + 2 * E * w["Hkv"] * w["D"] + HD * E + 2 * w["D"]
    experts = (E * w["Ne"] + 3 * E * w["Fs"] + E
               + w["held"] * 3 * E * w["Fm"])
    n_linear = sum(k == LINEAR for k in w["kinds"])
    return (n_linear * linear + (w["L"] - n_linear) * full
            + w["L"] * (experts + 2 * E) + 2 * w["V"] * E + E)


def delta_rule_flops_per_token(w: Mapping[str, Any]) -> float:
    """Forward operations a token of the chunked rule, all value heads: the
    five triangular products ``K K^T``, ``T (K)``, ``T (V)``, ``Q K^T`` and
    its product with ``V'`` by their triangles (``C`` rows a token over two),
    the inverse (``C^2 / 3``), and the three products with the state."""
    C, Dk, Dv = GDN_CHUNK, w["Dk"], w["Dv"]
    return w["Hv"] * (C * (3 * Dk + 2 * Dv) + C * C / 3.0
                      + 3 * 2.0 * Dk * Dv)


def layer_forward_flops(cfg: Mapping[str, Any], seq: int
                        ) -> List[Dict[str, float]]:
    """Forward operations for one token, layer by layer and part by part."""
    w = _w(cfg)
    E = w["E"]
    conv_ch = 2 * w["Hk"] * w["Dk"] + w["Hv"] * w["Dv"]
    vd, HD = w["Hv"] * w["Dv"], w["H"] * w["D"]
    expert = {"router": 2.0 * E * w["Ne"],
              "shared": 3 * 2.0 * E * w["Fs"] + 2.0 * E,
              "routed": (w["K"] * w["held"] / w["Ne"]) * 3 * 2.0 * E * w["Fm"]}
    linear = {"proj": 2.0 * E * (conv_ch + vd + 2 * w["Hv"]) + 2.0 * vd * E,
              "conv": 2.0 * w["Kc"] * conv_ch,
              "scan": delta_rule_flops_per_token(w)}
    full = {"proj": 2.0 * E * (2 * HD + 2 * w["Hkv"] * w["D"]) + 2.0 * HD * E,
            "attn": 2 * (2.0 * w["D"] * w["H"] * (seq + 1) / 2)}
    return [{**(linear if kind == LINEAR else full), **expert}
            for kind in w["kinds"]]


def forward_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    w = _w(cfg)
    return sum(sum(p.values()) for p in layer_forward_flops(cfg, seq)) \
        + 2.0 * w["E"] * w["V"]


def train_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    """Forward and backward: the backward of a matmul is two matmuls."""
    return 3.0 * forward_flops_per_token(cfg, seq)
