"""Builder ``mamba2_moe_decoder``: the Nemotron-H block stack: every block is
ONE norm and ONE sub-block with the residual around it, and the sub-block is
a Mamba-2 state-space mixer (``M``), an expert MLP (``E``: sigmoid router,
two-matrix relu^2 experts, a shared expert twice as wide, **a share of the
routed experts** held here) or full attention (``*``: 32 query heads on 2
key/value heads, no position signal at all), in the order
``hybrid_override_pattern`` gives; an untied head. A configuration names
this file by ``"builder"``.

``published_layers`` lists the published block indices that run; block ``i``
is of the kind ``hybrid_override_pattern[i]``. What a builder gives the
harness is listed in ``dense_gqa_decoder.py``; the reference's rounding
sites are that file's plus ``router``, ``state`` (the recurrence's state as
it passes from one block of ``STATE_BLOCK`` tokens to the next: where the
program passes it from chunk to chunk) and ``decay`` (the step ``Delta``,
the log decay ``Delta A`` and its exponential). ``drop_carry`` is a switch
and no rounding, as in ``gdn_moe_decoder.py``: the precision given is
ignored and the state entering each block of ``STATE_BLOCK`` tokens is zero
(the control ``no_carry``: a scan that loses what it carries).

The equations, ``N`` RMSNorm (``y = w x / sqrt(mean x^2 + eps)``), block
``i``: ``x <- x + F_i(N_i(x))``, ``u = N_i(x)``:

    M: [z | xBC | dt] = u W_in       (widths H P | H P + 2 G S | H, no bias)
      xBC <- SiLU(conv(xBC) + b_c): y_t = sum_{j<4} c_j * x_{t-3+j}, zeros
      left of the sequence;  [x | B | C] = xBC (H P | G S | G S), x as
      [T, H, P], B and C as [T, G, S], head h reading group h // (H / G)
      Delta = softplus(dt + dt_bias + shift);  A = -exp(A_log)   (a head)
      S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T   ([P, S] a head)
      y_t = S_t C_t + D x_t
      y <- N_group(y * SiLU(z)) * w_n, the mean square over each group's
      H P / G channels, the gate BEFORE the norm;  F = y W_out
    E: s = sigmoid(u W_r) over all experts (float32);  T = the top_k of s
      (n_group = topk_group = 1: plain top k);
      w_e = scale * s_e / (sum_T s + 1e-20);  f_W(u) = relu(u W_up)^2 W_down
      F = f_shared(u) + sum_{e in T, held} w_e f_e(u)
    *: q = u W_q (H_q heads x D), k, v = u W_k, u W_v (H_kv heads x D);
      a = softmax_causal(q k^T / sqrt(D)) v;  F = a W_o.  No bias, no
      rotary, no other position signal.
    loss = mean CE(N_f(x)_i W_head, t_{i+1}); the embedding untied, unscaled

``dt_bias_shift`` (the configuration's; added to the seeded ``dt_bias`` leaf
by the program's loss function and by the reference alike) is there because
the harness seeds every one-dimensional leaf at one, under which a state
forgets everything within a few tokens and a scan that dropped its carry
could not be told from a sound one (``assumed`` in the configuration's file
has the readings).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

# The parent of the PR that brought this builder has no such module: a cell
# of this builder then fails here, when the driver loads the builder.
import torchft_tpu.models.mamba2  # noqa: F401

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}

# What --rehearse shrinks the sizes to. Never a cell; never a device number.
# Every expert is selected (top 4 of 4) and half are held, as in
# ``afmoe_decoder.py`` and for its reason; 256 tokens are two chunks of the
# scan, so that a state is carried.
REHEARSE = dict(hidden_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, mamba_num_heads=4,
                mamba_head_dim=16, n_groups=2, ssm_state_size=16,
                moe_intermediate_size=64,
                moe_shared_expert_intermediate_size=128, n_routed_experts=4,
                num_experts_per_tok=4, num_experts_held=2, vocab_size=512)
REHEARSE_SEQ = 256

CONTROLS: Dict[str, Dict[str, str]] = {
    # the step below the bfloat16 that matmul inputs are stated in (the
    # router's own product stays float32, as stated)
    "fp8_matmul": {"matmul": "float8_e4m3/forward"},
    # the state zeroed at every boundary of 128 tokens (a switch: see above)
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

STATE_BLOCK = 128   # tokens of the reference's recurrence between two
#                     checkpoints of its state (and ``no_carry``'s boundary)


# ------------------------------------------------------------- the sizes

def _w(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    layers = [int(i) for i in cfg["published_layers"]]
    if len(layers) != int(cfg["num_hidden_layers"]):
        raise ValueError(f"published_layers names {len(layers)} layers, "
                         f"num_hidden_layers is {cfg['num_hidden_layers']}")
    pattern = str(cfg["hybrid_override_pattern"])
    kinds = [KINDS[pattern[i]] for i in layers]
    first = int(cfg.get("first_expert_held", 0))
    held = int(cfg["num_experts_held"])
    if first + held > int(cfg["n_routed_experts"]):
        raise ValueError("experts held beyond n_routed_experts")
    if int(cfg["n_group"]) != 1 or int(cfg["topk_group"]) != 1 \
            or int(cfg["n_shared_experts"]) != 1:
        raise ValueError("plain top-k over one group of experts and one "
                         "shared expert are written here")
    if (cfg["mlp_hidden_act"], cfg["mamba_hidden_act"]) != ("relu2", "silu") \
            or cfg["mamba_proj_bias"] or cfg["attention_bias"] \
            or cfg["mlp_bias"] or not cfg["use_conv_bias"]:
        raise ValueError("relu2 experts, a SiLU mixer, a convolution bias "
                         "and no other bias are written here")
    H, G = int(cfg["mamba_num_heads"]), int(cfg["n_groups"])
    if H % G:
        raise ValueError(f"{H} mamba heads over {G} groups")
    return dict(E=int(cfg["hidden_size"]), Hq=int(cfg["num_attention_heads"]),
                Hkv=int(cfg["num_key_value_heads"]), D=int(cfg["head_dim"]),
                H=H, P=int(cfg["mamba_head_dim"]), G=G,
                S=int(cfg["ssm_state_size"]), Kc=int(cfg["conv_kernel"]),
                Fm=int(cfg["moe_intermediate_size"]),
                Fs=int(cfg["moe_shared_expert_intermediate_size"]),
                V=int(cfg["vocab_size"]), L=len(layers),
                Ne=int(cfg["n_routed_experts"]),
                K=int(cfg["num_experts_per_tok"]),
                first=first, held=held, kinds=kinds,
                route_norm=bool(cfg["norm_topk_prob"]),
                route_scale=float(cfg["routed_scaling_factor"]),
                eps=float(cfg["layer_norm_epsilon"]),
                shift=float(cfg["dt_bias_shift"]))


# ----------------------------------------------------- the program's model

def _make_model(cfg: Mapping[str, Any], seq: int, interpret: bool,
                dtype: Any = jnp.bfloat16, remat: bool = False) -> Any:
    """The program's model at the configuration's sizes: ``Transformer``
    with single-sub-block layers of the pattern's kinds, the flash kernel at
    the attention block's head size (key/value heads shared through its
    index maps) with rotary off, and the routed relu^2 expert layer over its
    share. No per-layer remat: ``TransformerConfig.remat`` frees nothing
    (XLA merges the recomputed forward with the first one, PERF.md section
    7), the chip's compiler rematerialises what the non-donated step's six
    trees leave no room for either way, and without the request it
    recomputes less (20.2 against 21.2 TFLOP a step by the compiled
    program's own count, 28.65-28.70 k against 28.00-28.11 k tokens/s on
    the chip, two same-seed pairs; PERF.md, PR 45). ``dtype`` and ``remat``
    are the tests'."""
    from torchft_tpu.models import Transformer
    from torchft_tpu.models.transformer import TransformerConfig
    from torchft_tpu.ops import flash_attention

    w = _w(cfg)
    if cfg.get("sliding_window") is not None:
        raise ValueError("no window is written here")
    attention = functools.partial(flash_attention, interpret=interpret)
    attention.supports_gqa = True
    tcfg = TransformerConfig(
        vocab_size=w["V"], num_layers=w["L"], embed_dim=w["E"],
        num_heads=w["Hq"], num_kv_heads=w["Hkv"], max_seq_len=seq,
        rms_norm_eps=w["eps"], attention_fn=attention, remat=remat,
        dtype=dtype, layer_types=tuple(w["kinds"]), attn_head_dim=w["D"],
        rope_full_layers=False, linear_conv_kernel=w["Kc"],
        ssm_heads=w["H"], ssm_head_dim=w["P"], ssm_groups=w["G"],
        ssm_state=w["S"],
        moe_experts=w["Ne"], moe_top_k=w["K"], moe_dispatch="routed",
        moe_dim=w["Fm"], moe_held=(w["first"], w["held"]),
        moe_shared_dim=w["Fs"], moe_form="relu2", moe_score="sigmoid",
        moe_route_norm=w["route_norm"], moe_route_scale=w["route_scale"],
        moe_interpret=interpret)
    return Transformer(tcfg)


def _shifted(params: Any, w: Mapping[str, Any]) -> Any:
    """``params`` with ``dt_bias_shift`` added to every mamba block's
    ``dt_bias``: the same as seeding the leaf that much lower, and the
    leaf's gradient is unchanged."""
    p = dict(params["params"])
    for i, kind in enumerate(w["kinds"]):
        if kind == "mamba":
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
    """``(params, tokens) -> [experts [T, K] of each expert block]``: what
    the program's routers select (``benchmarks/route_flips.py``)."""
    model = _make_model(cfg, seq, interpret)
    w = _w(cfg)

    def selections(params, tokens):
        _, state = model.apply(_shifted(params, w), tokens,
                               return_hidden=True, mutable=["intermediates"])
        return [state["intermediates"][f"layer_{i}"]["moe"]["experts"][0]
                for i, kind in enumerate(w["kinds"]) if kind == "moe"]

    return selections


# ------------------------------------------------------------- the shapes

def param_shapes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    """The parameter tree's shapes (all float32), named as the program's
    ``Transformer`` names them. One-dimensional leaves (norm gains, the
    convolution's bias, ``A_log``, ``dt_bias``, ``D``) are made as ones, the
    others normal(0, initializer_range): the convolution's ``[kernel,
    channels]`` among them."""
    w = _w(cfg)
    E = w["E"]
    inner = w["H"] * w["P"]
    conv_ch = inner + 2 * w["G"] * w["S"]
    mamba = {"in_proj": {"kernel": (E, inner + conv_ch + w["H"])},
             "conv": (w["Kc"], conv_ch), "conv_bias": (conv_ch,),
             "A_log": (w["H"],), "dt_bias": (w["H"],), "D": (w["H"],),
             "norm": (inner,), "out_proj": {"kernel": (inner, E)}}
    Hq, Hkv, D = w["Hq"], w["Hkv"], w["D"]
    attn = {"q": {"kernel": (E, Hq, D)}, "k": {"kernel": (E, Hkv, D)},
            "v": {"kernel": (E, Hkv, D)}, "o": {"kernel": (Hq * D, E)}}
    moe: Dict[str, Any] = {
        "router": {"kernel": (E, w["Ne"])},
        "shared": {"up": {"kernel": (E, w["Fs"])},
                   "down": {"kernel": (w["Fs"], E)}}}
    if w["held"]:
        moe.update(wi_up=(w["held"], E, w["Fm"]),
                   wo=(w["held"], w["Fm"], E))
    tree: Dict[str, Any] = {"embed": {"embedding": (w["V"], E)},
                            "final_norm": {"scale": (E,)},
                            "lm_head": {"kernel": (E, w["V"])}}
    for i, kind in enumerate(w["kinds"]):
        block: Dict[str, Any] = {"norm": {"scale": (E,)}}
        if kind == "moe":
            block["moe"] = moe
        else:
            block["attn"] = mamba if kind == "mamba" else attn
        tree[f"layer_{i}"] = block
    return {"params": tree}


# ---------------------------------------------------- the plain reference

def _same(x):
    return x


def _rms_norm(x, scale, eps, r):
    x = r(x)
    mean_sq = r(jnp.mean(r(x * x), axis=-1, keepdims=True))
    return r(r(x * r(jax.lax.rsqrt(mean_sq + eps))) * scale)


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


def _attention_block(u, a, w, r):
    mm = r.get("matmul", _same)
    q = jnp.einsum("bse,ehd->bshd", mm(u), mm(a["q"]["kernel"]))
    k = jnp.einsum("bse,ehd->bshd", mm(u), mm(a["k"]["kernel"]))
    v = jnp.einsum("bse,ehd->bshd", mm(u), mm(a["v"]["kernel"]))
    o = _attention(q, k, v, mm, r.get("softmax", _same))
    return mm(o) @ mm(a["o"]["kernel"])


def _causal_conv(x, c, bias):
    """x [B, S, Ch], c [K, Ch], bias [Ch]: y_t = sum_j c_j * x_{t-K+1+j} +
    bias, zeros left of the sequence."""
    K, S = c.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(c[j] * xp[:, j: j + S] for j in range(K)) + bias


def state_space_by_token(x, delta, a, b_in, c_in, st=_same, dc=_same,
                         carry: bool = True):
    """The state-space recurrence token by token, without the skip term:
    x [B,S,H,P], delta [B,S,H], a [H], b_in and c_in [B,S,H,N] (already one
    a head) -> [B,S,H,P]. The recurrence runs in blocks of ``STATE_BLOCK``
    tokens only so that its backward fits: the state entering a block is
    kept, the states inside are recomputed. ``st`` is put on the state as
    it passes from block to block, ``dc`` on the decays; ``carry=False``
    passes zeros."""
    B, S, H, P = x.shape
    n = -(-S // STATE_BLOCK)
    pad = n * STATE_BLOCK - S

    def blocks(v):   # [B, S, H, ...] -> [n, STATE_BLOCK, B, H, ...]
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(n, STATE_BLOCK, *v.shape[1:])

    def token(state, xs):
        x_t, d_t, b_t, c_t = xs
        keep = dc(jnp.exp(dc(d_t * a)))
        state = keep[..., None, None] * state \
            + (d_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    @jax.checkpoint
    def block(state, xs):
        state, out = jax.lax.scan(token, state, xs)
        state = st(state)
        return (state if carry else jnp.zeros_like(state)), out

    # a padded token has step zero: it keeps the state and writes nothing
    _, out = jax.lax.scan(
        block, jnp.zeros((B, H, P, b_in.shape[-1]), jnp.float32),
        (blocks(x), blocks(delta), blocks(b_in), blocks(c_in)))
    out = out.reshape(n * STATE_BLOCK, B, H, P)[:S]
    return jnp.moveaxis(out, 0, 1)


def _mamba_block(u, a, w, r):
    mm, nrm = r.get("matmul", _same), r.get("norm", _same)
    dc = r.get("decay", _same)
    B, S, _ = u.shape
    H, P, G, N = w["H"], w["P"], w["G"], w["S"]
    inner = H * P
    conv_ch = inner + 2 * G * N
    zxbcdt = mm(u) @ mm(a["in_proj"]["kernel"])
    z = zxbcdt[..., :inner]
    xbc = jax.nn.silu(_causal_conv(zxbcdt[..., inner: inner + conv_ch],
                                   a["conv"], a["conv_bias"]))
    dt = zxbcdt[..., inner + conv_ch:]
    x = xbc[..., :inner].reshape(B, S, H, P)

    def heads(v):           # [B, S, G * N] -> one a head, [B, S, H, N]
        return jnp.repeat(v.reshape(B, S, G, N), H // G, axis=2)

    b_in = heads(xbc[..., inner: inner + G * N])
    c_in = heads(xbc[..., inner + G * N:])
    delta = dc(jax.nn.softplus(dt + a["dt_bias"] + w["shift"]))
    y = state_space_by_token(mm(x), delta, -jnp.exp(a["A_log"]), mm(b_in),
                             mm(c_in), r.get("state", _same), dc,
                             "drop_carry" not in r)
    y = y + a["D"][:, None] * x
    y = y.reshape(B, S, inner) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(B, S, G, inner // G), 1.0, w["eps"], nrm)
    return mm(y.reshape(B, S, inner) * a["norm"]) @ mm(a["out_proj"]["kernel"])


def _relu2(u, up, down, mm):
    return mm(jnp.square(jax.nn.relu(mm(u) @ mm(up)))) @ mm(down)


def reference_routing(u, router_kernel, w: Mapping[str, Any], rt=_same
                      ) -> Tuple[Any, Any]:
    """The selection: ``(weights [B,S,K], experts [B,S,K])``."""
    s = rt(jax.nn.sigmoid(rt(u) @ rt(router_kernel)))
    top, idx = jax.lax.top_k(s, w["K"])
    if w["route_norm"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return top * w["route_scale"], idx


def _experts(u, p, w: Mapping[str, Any], mm, rt, collect=None):
    """The expert block's part for the held experts: the obvious loop over
    them, each computing every token under a mask of the pairs routed to
    it, and the shared expert."""
    weights, idx = reference_routing(u, p["router"]["kernel"], w, rt)
    if collect is not None:
        collect.append(idx.reshape(-1, idx.shape[-1]))
    sh = p["shared"]
    m = _relu2(u, sh["up"]["kernel"], sh["down"]["kernel"], mm)
    one = jax.checkpoint(functools.partial(_relu2, mm=mm))
    for e in range(w["held"]):
        w_e = jnp.sum(jnp.where(idx == w["first"] + e, weights, 0.0), axis=-1)
        m = m + w_e[..., None] * one(u, p["wi_up"][e], p["wo"][e])
    return m


def _one_block(x, lp, w, kind, r, collect):
    res, nrm = r.get("residual", _same), r.get("norm", _same)
    u = _rms_norm(x, lp["norm"]["scale"], w["eps"], nrm)
    if kind == "mamba":
        f = _mamba_block(u, lp["attn"], w, r)
    elif kind == "attention":
        f = _attention_block(u, lp["attn"], w, r)
    else:
        f = _experts(u, lp["moe"], w, r.get("matmul", _same),
                     r.get("router", _same), collect)
    return res(x + f)


def _block(x, lp, w, kind, r, collect):
    """One block; without ``collect`` recomputed in the backward, so that
    seven blocks' float32 intermediates at 8192 tokens fit beside the tree
    and its gradients."""
    if collect is None:
        return jax.checkpoint(
            lambda x_, lp_: _one_block(x_, lp_, w, kind, r, None))(x, lp)
    return _one_block(x, lp, w, kind, r, collect)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _mean_nll(states, head, targets, mm, lg):
    logits = lg(mm(states) @ mm(head))
    logp = lg(jax.nn.log_softmax(logits, axis=-1))
    return jnp.mean(-jnp.take_along_axis(logp, targets[..., None], axis=-1))


def reference_selections(params: Any, tokens: Any, cfg: Mapping[str, Any],
                         rounding: Optional[Mapping[str, Callable]] = None
                         ) -> List[Any]:
    """``[experts [T, K] of each expert block]`` as the reference selects
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
    the router's, the recurrence's x, B and C among them), ``router`` (its
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
            x = _block(x, p[f"layer_{i}"], w, kind, r, collect)
        x = _rms_norm(x, p["final_norm"]["scale"], w["eps"],
                      r.get("norm", _same))
        return _mean_nll(x[:, :-1], p["lm_head"]["kernel"], tokens[:, 1:],
                         mm, r.get("logits", _same))


# ------------------------------------------- what the algorithm needs
#
# Needed work only (``dense_gqa_decoder.py`` says what that leaves out): the
# causal triangle of the attention block, of the routed experts the expected
# ``top_k * held / n_routed_experts`` a token, and of a mamba block's scan
# the products of the chunked form at the program's chunk, the one inside a
# chunk by its triangle: the token-by-token form does fewer operations and
# is no way to run a matrix unit.

SSD_CHUNK = 128   # the chunk the operations are counted at (ops/ssd.py)


def param_count(cfg: Mapping[str, Any]) -> int:
    """Parameters of the decoder as run, from the configuration alone."""
    w = _w(cfg)
    E = w["E"]
    inner = w["H"] * w["P"]
    conv_ch = inner + 2 * w["G"] * w["S"]
    mamba = (E * (inner + conv_ch + w["H"]) + (w["Kc"] + 1) * conv_ch
             + 3 * w["H"] + inner + inner * E)
    HD = w["Hq"] * w["D"]
    attn = 2 * E * HD + 2 * E * w["Hkv"] * w["D"]
    experts = (E * w["Ne"] + 2 * E * w["Fs"] + w["held"] * 2 * E * w["Fm"])
    per = {"mamba": mamba, "attention": attn, "moe": experts}
    return (sum(per[k] + E for k in w["kinds"]) + 2 * w["V"] * E + E)


def ssd_flops_per_token(w: Mapping[str, Any], seq: int) -> float:
    """Forward operations a token of the chunked scan, all heads, at a chunk
    of ``C``: ``C B^T`` once a group and its product with ``Delta X`` once a
    head, both by their triangles (``C / 2`` columns a token); the chunk's
    state ``X^T B`` and its reading ``C S``, ``2 P N`` each a head; the pass
    between chunks, ``T / C`` chunks each reading on average half of them:
    ``2 P N (T / C) / 2`` a chunk, over its ``C`` tokens."""
    C, H, G, P, N = SSD_CHUNK, w["H"], w["G"], w["P"], w["S"]
    chunks = -(-seq // C)
    return (G * C * N + H * C * P + 2 * H * 2.0 * P * N
            + H * P * N * chunks / C)


def layer_forward_flops(cfg: Mapping[str, Any], seq: int
                        ) -> List[Dict[str, float]]:
    """Forward operations for one token, block by block and part by part."""
    w = _w(cfg)
    E = w["E"]
    inner = w["H"] * w["P"]
    conv_ch = inner + 2 * w["G"] * w["S"]
    HD = w["Hq"] * w["D"]
    per = {
        "moe": {"router": 2.0 * E * w["Ne"], "shared": 2 * 2.0 * E * w["Fs"],
                "routed": (w["K"] * w["held"] / w["Ne"])
                * 2 * 2.0 * E * w["Fm"]},
        "mamba": {"proj": 2.0 * E * (inner + conv_ch + w["H"])
                  + 2.0 * inner * E,
                  "conv": 2.0 * w["Kc"] * conv_ch,
                  "scan": ssd_flops_per_token(w, seq)},
        "attention": {"proj": 2.0 * E * (HD + 2 * w["Hkv"] * w["D"])
                      + 2.0 * HD * E,
                      "attn": 2 * (2.0 * w["D"] * w["Hq"] * (seq + 1) / 2)}}
    return [dict(per[kind]) for kind in w["kinds"]]


def forward_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    w = _w(cfg)
    return sum(sum(p.values()) for p in layer_forward_flops(cfg, seq)) \
        + 2.0 * w["E"] * w["V"]


def train_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    """Forward and backward: the backward of a matmul is two matmuls."""
    return 3.0 * forward_flops_per_token(cfg, seq)
