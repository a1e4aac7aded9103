"""Builder ``looped_dense_decoder``: a looped ("universal") dense decoder
(Ouro-2.6B, arXiv:2510.25741): ONE stack of sandwich-normed layers (plain
multi-head attention with full rotary, SwiGLU, no biases) and its final norm
run ``total_ut_steps`` times over the same weights, an untied head on the
output of EVERY pass, and a learned exit gate whose exit distribution
weights the passes' losses. A configuration names this file by
``"builder"``.

What a builder gives the harness is listed in ``dense_gqa_decoder.py``; the
reference's rounding sites are that file's (``matmul``, ``residual``,
``norm``, ``softmax``, ``logits``). ``one_pass`` and ``uniform_exits`` are
switches and no roundings, as ``drop_taps`` is in ``lfm2_moe_decoder.py``:
the precision given is ignored; under ``one_pass`` the reference runs one
pass and puts the whole loss on it (what a program that drops the loop
computes), under ``uniform_exits`` it weights the ``T`` exits ``1 / T`` each
and ignores the gate (what a program whose exit weights are not the gate's
computes).

The equations, ``N`` RMSNorm with a learned gain (``y = w x / sqrt(mean x^2
+ eps)``), ``E`` hidden, ``S`` tokens, ``L`` layers held, ``T`` passes:

    layer: a = Attn(N1(x)); x = x + N2(a); u = N3(x);
      m = (SiLU(u W_gate) * u W_up) W_down; x = x + N4(m)
      Attn(h): q, k, v = h W_q, h W_k, h W_v as H heads of D; rotary on q
      and k over the whole head, pairs (i, i + D/2), theta rope_theta;
      softmax_causal(q k^T / sqrt(D)) v; then W_o
    loop: x_0 = Embed[tokens]; for t = 1..T:
      h_t = N_f(Layer_{L-1}(... Layer_0(x_{t-1}))); x_t = h_t
      (the same L layers and the same N_f every pass)
    gate, a position: lambda_t = sigmoid(h_t . w_g + b_g + shift), t < T
      (w_g and b_g one leaf [E + 1, 1], b_g its last row);
      lambda_T = 1; q_t = lambda_t prod_{j<t} (1 - lambda_j)
    loss over positions i = 0..S-2, l_t(i) = -log softmax(h_t(i) W_head)
      [token_{i+1}]:  mean_i [ sum_t q_t(i) l_t(i) - beta H(q(i)) ],
      H(q) = -sum_t q_t log q_t

``shift`` is the configuration's ``exit_gate_bias_shift``, a constant added
to the gate's logit by the program's loss and by the reference alike (the
harness seeds the gate's leaf N(0, initializer_range), ``b_g`` near zero,
which alone would halve the stream at every exit); ``beta`` its
``exit_entropy_weight``.

Departures of the reference from the obvious program of these equations,
none of the mathematics: a ``jax.checkpoint`` around every layer application
(``L x T`` of them), so that its gradient fits at 8,192 tokens; attention a
head at a time and the head's cross-entropy in blocks of rows (a plain
``lax.map``, no rule of its own), so that one ``[S, S]`` score matrix and
one block's float32 logits are live and not sixteen and ``T`` whole exits'.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Mapping, Optional

import jax
import jax.numpy as jnp

# The parent of the PR that brought this builder has no looped stack: a cell
# of this builder then fails here, when the driver loads the builder.
from torchft_tpu.models.transformer import (  # noqa: F401
    looped_causal_lm_loss)

# What --rehearse shrinks the sizes to. Never a cell; never a device number.
# Two layers, four passes: at these widths and 64 tokens a leaf has too few
# rows behind it for 24 bfloat16 layer applications to stay well under the
# cell's limit of 0.1 (read 0.082 at six layers, 0.020-0.026 at two).
REHEARSE = dict(hidden_size=128, num_attention_heads=4,
                num_key_value_heads=4, head_dim=32, intermediate_size=256,
                vocab_size=512, num_hidden_layers=2)
REHEARSE_SEQ = 64

CONTROLS: Dict[str, Dict[str, str]] = {
    # the step below the bfloat16 that matmul inputs are stated in
    "fp8_matmul": {"matmul": "float8_e4m3/forward"},
    # switches (see above): the loop dropped; the gate's weights dropped
    "one_pass": {"one_pass": "bfloat16/forward"},
    "uniform_exits": {"uniform_exits": "bfloat16/forward"},
}
PROBES: Dict[str, Dict[str, str]] = {
    "stated_bf16": {"matmul": "bfloat16/forward", "residual": "bfloat16"},
    "bf16_islands": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                     "norm": "bfloat16", "softmax": "bfloat16",
                     "logits": "bfloat16"},
}
HEAD_ROWS = 1024     # rows of the head a block of the reference's loss


# ------------------------------------------------------------- the sizes

def _w(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    E, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    if int(cfg["num_key_value_heads"]) != H:
        raise ValueError("plain multi-head attention is written here: "
                         f"{cfg['num_key_value_heads']} key/value heads "
                         f"for {H} query heads")
    kinds = cfg.get("layer_types")
    if kinds and set(kinds) != {"full_attention"}:
        raise ValueError(f"layer kinds {sorted(set(kinds))}: full_attention "
                         "is written here")
    if float(cfg.get("early_exit_threshold", 1)) != 1:
        raise ValueError("every pass runs for every token "
                         "(early_exit_threshold 1) is written here")
    return dict(E=E, H=H, D=int(cfg["head_dim"]),
                F=int(cfg["intermediate_size"]), V=int(cfg["vocab_size"]),
                L=int(cfg["num_hidden_layers"]),
                T=int(cfg["total_ut_steps"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                beta=float(cfg["exit_entropy_weight"]),
                shift=float(cfg["exit_gate_bias_shift"]))


# ----------------------------------------------------- the program's model

def _make_model(cfg: Mapping[str, Any], seq: int, interpret: bool,
                dtype: Any = jnp.bfloat16,
                loop_steps: Optional[int] = None) -> Any:
    """The program's model at the configuration's sizes: ``Transformer``
    with four norms a layer, the flash kernel at 16/16 heads of 128, the
    stack as one scan over ``total_ut_steps`` passes with every layer
    rematerialised inside it, and the exit gate. ``dtype`` and
    ``loop_steps`` are the tests'."""
    from torchft_tpu.models import Transformer
    from torchft_tpu.models.transformer import TransformerConfig
    from torchft_tpu.ops import flash_attention

    w = _w(cfg)
    tcfg = TransformerConfig(
        vocab_size=w["V"], num_layers=w["L"], embed_dim=w["E"],
        num_heads=w["H"], num_kv_heads=w["H"], attn_head_dim=w["D"],
        hidden_dim=w["F"], max_seq_len=seq, rope_theta=w["theta"],
        rms_norm_eps=w["eps"], sandwich_norm=True,
        attention_fn=functools.partial(flash_attention, interpret=interpret),
        remat=True, dtype=dtype, exit_gate=True,
        loop_steps=w["T"] if loop_steps is None else loop_steps)
    return Transformer(tcfg)


def make_loss_fn(cfg: Mapping[str, Any], seq: int, interpret: bool,
                 **model_kw: Any) -> Callable:
    """The program's loss: the model above under ``looped_causal_lm_loss``
    (the exits through one fused head scan, the gate's exit distribution
    their weights, the entropy term beside it)."""
    model = _make_model(cfg, seq, interpret, **model_kw)
    w = _w(cfg)

    def loss_fn(params, batch):
        return looped_causal_lm_loss(model, params, batch["tokens"],
                                     w["beta"], w["shift"])

    return loss_fn


# ------------------------------------------------------------- the shapes

def param_shapes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    """The parameter tree's shapes (all float32), named as the program's
    ``Transformer`` names them: ONE set of layers whatever the passes, and
    the gate's one leaf (the bias its last row). One-dimensional leaves
    (norm gains) are made as ones, the others normal(0,
    initializer_range)."""
    w = _w(cfg)
    E, H, D, F = w["E"], w["H"], w["D"], w["F"]
    norm = {"scale": (E,)}
    layer = {
        "attn_norm": norm, "post_attn_norm": norm,
        "attn": {"q": {"kernel": (E, H, D)}, "k": {"kernel": (E, H, D)},
                 "v": {"kernel": (E, H, D)}, "o": {"kernel": (H * D, E)}},
        "mlp_norm": norm, "post_mlp_norm": norm,
        "mlp": {"gate": {"kernel": (E, F)}, "up": {"kernel": (E, F)},
                "down": {"kernel": (F, E)}},
    }
    tree: Dict[str, Any] = {"embed": {"embedding": (w["V"], E)},
                            "final_norm": norm,
                            "exit_gate": {"kernel": (E + 1, 1)},
                            "lm_head": {"kernel": (E, w["V"])}}
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
    """x: [B, S, H, D]; rotate the pairs (i, i + D/2) by
    position * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v, mm, soft):
    """Causal softmax attention, q, k, v [B,S,H,D], one head at a time, so
    that the [S, S] scores of an 8192-token sequence stay 256 MiB."""
    B, S, H, D = q.shape
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    @jax.checkpoint
    def one(args):
        q1, k1, v1 = args
        s = soft(jnp.einsum("bqd,bkd->bqk", mm(q1), mm(k1)) * (D ** -0.5))
        p = soft(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1))
        return jnp.einsum("bqk,bkd->bqd", mm(p), mm(v1))

    out = jax.lax.map(one, tuple(x.transpose(2, 0, 1, 3) for x in (q, k, v)))
    return out.transpose(1, 2, 0, 3).reshape(B, S, H * D)


def _one_layer(x, lp, w, r):
    mm, res = r.get("matmul", _same), r.get("residual", _same)
    nrm = r.get("norm", _same)
    a = lp["attn"]
    h = _rms_norm(x, lp["attn_norm"]["scale"], w["eps"], nrm)
    q = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["q"]["kernel"]))
    k = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["k"]["kernel"]))
    v = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["v"]["kernel"]))
    o = _attention(_rope(q, w["theta"]), _rope(k, w["theta"]), v, mm,
                   r.get("softmax", _same))
    o = mm(o) @ mm(a["o"]["kernel"])
    x = res(x + _rms_norm(o, lp["post_attn_norm"]["scale"], w["eps"], nrm))
    u = _rms_norm(x, lp["mlp_norm"]["scale"], w["eps"], nrm)
    m = lp["mlp"]
    gate = mm(u) @ mm(m["gate"]["kernel"])
    up = mm(u) @ mm(m["up"]["kernel"])
    m = mm(jax.nn.silu(gate) * up) @ mm(m["down"]["kernel"])
    return res(x + _rms_norm(m, lp["post_mlp_norm"]["scale"], w["eps"], nrm))


def _exit_nll(states, head, targets, mm, lg):
    """``l(i) = -log softmax(states_i W_head)[target_i]`` for every row,
    float32 [B, S-1], ``HEAD_ROWS`` rows of the head at a time."""
    B, S1, E = states.shape
    rows = min(HEAD_ROWS, S1)
    pad = -S1 % rows
    st = jnp.pad(states, ((0, 0), (0, pad), (0, 0)))
    tg = jnp.pad(targets, ((0, 0), (0, pad)))

    @jax.checkpoint
    def block(args):
        s_b, t_b = args
        logits = lg(mm(s_b) @ mm(head))
        logp = lg(jax.nn.log_softmax(logits, axis=-1))
        return -jnp.take_along_axis(logp, t_b[..., None], axis=-1)[..., 0]

    nll = jax.lax.map(block, (
        st.reshape(B, -1, rows, E).transpose(1, 0, 2, 3),
        tg.reshape(B, -1, rows).transpose(1, 0, 2)))
    return nll.transpose(1, 0, 2).reshape(B, -1)[:, :S1]


def reference_loss(params: Any, tokens: Any, cfg: Mapping[str, Any],
                   rounding: Optional[Mapping[str, Callable]] = None) -> Any:
    """The loss of the module docstring for ``tokens`` [B, S] in float32 at
    the highest matmul precision. ``rounding`` maps a site to a function put
    on every value there (``matmul``, ``residual``, ``norm``, ``softmax``,
    ``logits``), and names the switches ``one_pass`` and ``uniform_exits``
    (the module docstring). A site that is not named is left in float32."""
    w = _w(cfg)
    r = dict(rounding or {})
    mm, lg = r.get("matmul", _same), r.get("logits", _same)
    p = params["params"]
    passes = 1 if "one_pass" in r else w["T"]
    layer = jax.checkpoint(lambda x_, lp_: _one_layer(x_, lp_, w, r))
    with jax.default_matmul_precision("highest"):
        x = r.get("residual", _same)(p["embed"]["embedding"][tokens])
        exits = []
        for _ in range(passes):
            for i in range(w["L"]):
                x = layer(x, p[f"layer_{i}"])
            x = _rms_norm(x, p["final_norm"]["scale"], w["eps"],
                          r.get("norm", _same))
            exits.append(x)
        losses = [_exit_nll(h[:, :-1], p["lm_head"]["kernel"],
                            tokens[:, 1:], mm, lg) for h in exits]
        if "one_pass" in r:
            return jnp.mean(losses[0])
        if "uniform_exits" in r:
            return jnp.mean(sum(losses)) / passes
        # the gate, a position: the probability of leaving at each pass
        gate = p["exit_gate"]["kernel"][:, 0]
        stay = jnp.ones_like(losses[0])
        total = jnp.zeros_like(losses[0])
        entropy = jnp.zeros_like(losses[0])
        for t, (h, nll) in enumerate(zip(exits, losses)):
            if t < passes - 1:
                logit = jnp.sum(h[:, :-1] * gate[:-1], axis=-1) + gate[-1] \
                    + w["shift"]
                lam = jax.nn.sigmoid(logit)
            else:
                lam = jnp.ones_like(stay)
            q = lam * stay
            stay = stay * (1.0 - lam)
            total = total + q * nll
            entropy = entropy - q * jnp.log(q)
        return jnp.mean(total - w["beta"] * entropy)


# ------------------------------------------- what the algorithm needs
#
# Needed work only (``dense_gqa_decoder.py`` says what that leaves out; the
# rematerialised forward of every layer is among it): every pass's body and
# causal triangle, a head product an exit, the gate's dot a gated exit.

def param_count(cfg: Mapping[str, Any]) -> int:
    """Parameters of the decoder as run: one set of layers (four norm gains
    each), untied embedding and head, the final norm, the gate."""
    w = _w(cfg)
    E, HD = w["E"], w["H"] * w["D"]
    layer = 4 * E * HD + 3 * E * w["F"] + 4 * E
    return w["L"] * layer + 2 * w["V"] * E + E + E + 1


def forward_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    """Forward operations for one token of a ``seq``-token causal sequence
    (the mean over its positions), all ``T`` passes."""
    w = _w(cfg)
    E, HD = w["E"], w["H"] * w["D"]
    proj = 2.0 * E * 3 * HD + 2.0 * HD * E
    mlp = 3 * 2.0 * E * w["F"]
    attn = 2 * (2.0 * seq * w["D"] * w["H"]) / 2
    head = 2.0 * E * w["V"]
    return w["T"] * (w["L"] * (proj + mlp + attn) + head) \
        + (w["T"] - 1) * 2.0 * E


def train_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    """Forward and backward: the backward of a matmul is two matmuls."""
    return 3.0 * forward_flops_per_token(cfg, seq)
