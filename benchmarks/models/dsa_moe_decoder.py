"""Builder ``dsa_moe_decoder``: a decoder whose attention is a LEARNED
SPARSE attention (the DeepSeek-V3.2-Exp report's, as Keye-VL-2.0's language
model carries it): grouped-query attention (RMSNorm of queries and keys per
head, rotary over the whole head) in which an indexer (a second, small
attention: ``J`` heads on ONE key head, a ReLU and a learned weight a head)
scores every earlier token and each query attends to its ``topk`` best; the
indexer reads a stopped copy of the stream and is trained by a loss of its
own beside the model's. Every layer's MLP an expert layer (softmax scores
over all experts, the top k normalised, no shared expert, **a share of the
routed experts** held here), pre-norm with two norms a layer, an untied
head. A configuration names this file by ``"builder"``.

The equations, ``N`` RMSNorm (eps from the configuration), ``h = N_1(x)``,
``sg`` a stopped gradient, ``H`` query heads on ``G`` key/value heads of
``d``, indexer heads ``J`` of ``c``, ``K = topk``:

    q, k, v = h W_q, h W_k, h W_v;  q, k <- rotary(N_head(q), N_head(k))
    g = sg(h);  a = rotary(g W_a) [J heads of c];
      b = rotary(LayerNorm(g W_b)) [one head of c];  u = g W_u [J]
      I[t,s] = (J c)^-1/2 sum_j u[t,j] relu(a[t,j] . b[s]),  s <= t
    S_t = the min(t + 1, K) largest of I[t, 0..t] (jax.lax.top_k: ties to
      the lower index)
    o[t,i] = sum_{s in S_t} softmax_{s in S_t}(q[t,i] . k[s,i//(H/G)] /
      sqrt(d)) v[s, i//(H/G)];  x <- x + concat_i(o) W_o
    p[t,.] = (1/H) sum_i P[t,i,.] on S_t;  r[t,.] = softmax_{S_t}(I[t,.])
    L_I = (1/S) sum_t sum_{s in S_t} sg(p) (log sg(p) - log r)
    u' = N_2(x);  pr = softmax(u' W_r);  T = the top_k of pr;
      w_e = pr_e / (sum_T pr + 1e-20);  x <- x + sum_{e in T, held} w_e
      SwiGLU_e(u')
    L = mean CE(N_f(x)_i W_head, t_{i+1}) + indexer_loss_weight sum_layers L_I

``embedding_rows_times_sqrt_hidden`` (the configuration's seeding, read in
``_w`` alone): a token's row enters the stream times ``sqrt(E)``, through the
program's ``embed_scale`` and in the reference's first line: the same as
seeding the embedding table at ``normal(0, initializer_range * sqrt(E))``
(rows of about unit size) where the harness seeds every matrix at
``initializer_range``. Without it the routers collapse: seeded attention is
diffuse, so what is common to every token passes through ``W_v W_o``
undiminished while what is a token's own averages out, the common part
grows about sixtyfold a layer against rows of 0.02, and by layer 1 nearly
every token selects the same eight experts (``assumed.seeding`` has the
loads). With unit rows the four layers held stay what a trained model's
first layers are: a stream that is mostly the token's own embedding.

``L_I`` reaches the indexer's leaves alone (``g`` and ``p`` are stopped)
and the cross-entropy every other leaf (the selection is a hard set). What a
builder gives the harness is listed in ``dense_gqa_decoder.py``; the
reference's rounding sites are that file's plus ``router`` and ``indexer``
(the index scores' ReLU, weighting and sum). Three switches ride the
rounding table as ``drop_carry`` does in ``gdn_moe_decoder.py`` (a site can
only be named with a precision, which is ignored): ``dense_attention`` (the
reference attends to every causal key), ``drop_indexer_loss`` (the
reference leaves ``L_I`` out) and ``half_topk`` (the reference selects
``K / 2``).

Departures of the reference from the plain form, so that it fits at 8,192
tokens: a ``jax.checkpoint`` a layer, attention and ``p`` a block of
``HEAD_BLOCK`` query heads at a time under a boolean ``[S, S]`` mask; and
so that it compiles in a minute and not in four: the held experts under a
``scan`` over their stacks (sixteen unrolled copies a layer of float32
matmuls at ``highest`` were 1.35 GB of code for the chip).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

# The program's loss, imported here and not where it is used: a checkout
# whose program has no learned sparse attention (the parent of PR 60)
# stops when the driver loads this file, at once, and not after its set-up.
# The reference below calls nothing of ``torchft_tpu``.
from torchft_tpu.models import sparse_lm_loss

# What --rehearse shrinks the sizes to. Never a cell; never a device number.
# Every expert is selected (top 4 of 4) and half are held, as in
# ``afmoe_decoder.py`` and for its reason; half the rows select all of their
# keys and half a true subset.
REHEARSE = dict(hidden_size=128, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, moe_intermediate_size=64,
                num_experts=4, num_local_experts=4, num_experts_per_tok=4,
                num_experts_held=2, vocab_size=512,
                sa_config=dict(indexer_head_dim=16, indexer_num_heads=2,
                               indexer_num_kv_heads=1, kv_chunk_size=512,
                               q_chunk_size=512, topk=32))
REHEARSE_SEQ = 64

CONTROLS: Dict[str, Dict[str, str]] = {
    # the step below the bfloat16 that matmul inputs are stated in (the
    # router's own product stays float32, as stated)
    "fp8_matmul": {"matmul": "float8_e4m3/forward"},
    # switches (see above): what a program without the selection, without
    # the indexer's loss, or with half the keys would match
    "dense_attention": {"dense_attention": "bfloat16/forward"},
    "no_indexer_loss": {"drop_indexer_loss": "bfloat16/forward"},
    "topk_half": {"half_topk": "bfloat16/forward"},
}
PROBES: Dict[str, Dict[str, str]] = {
    "stated_bf16": {"matmul": "bfloat16/forward", "residual": "bfloat16"},
    "bf16_router": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                    "router": "bfloat16/forward"},
    # the index scores' ReLU, weighting and sum lowered too
    "bf16_indexer": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                     "indexer": "bfloat16/forward"},
    "bf16_islands": {"matmul": "bfloat16/forward", "residual": "bfloat16",
                     "norm": "bfloat16", "softmax": "bfloat16",
                     "logits": "bfloat16"},
}

HEAD_BLOCK = 4    # query heads of the reference's attention at a time


# ------------------------------------------------------------- the sizes

def _w(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    layers = [int(i) for i in cfg["published_layers"]]
    if len(layers) != int(cfg["num_hidden_layers"]):
        raise ValueError(f"published_layers names {len(layers)} layers, "
                         f"num_hidden_layers is {cfg['num_hidden_layers']}")
    if cfg["mlp_only_layers"] or int(cfg["decoder_sparse_step"]) != 1:
        raise ValueError("every layer's MLP is an expert layer here")
    if cfg.get("attention_bias") or cfg.get("use_sliding_window"):
        raise ValueError("no bias and no window are written here")
    sa = cfg["sa_config"]
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("the indexer has ONE key head here")
    first = int(cfg.get("first_expert_held", 0))
    held = int(cfg["num_experts_held"])
    if first + held > int(cfg["num_experts"]):
        raise ValueError("experts held beyond num_experts")
    H, G = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    if H % G or (H // G) % min(HEAD_BLOCK, H // G):
        raise ValueError(f"{H} query heads on {G} key/value heads")
    return dict(E=int(cfg["hidden_size"]), H=H, G=G, D=int(cfg["head_dim"]),
                J=int(sa["indexer_num_heads"]), C=int(sa["indexer_head_dim"]),
                topk=int(sa["topk"]), Fm=int(cfg["moe_intermediate_size"]),
                V=int(cfg["vocab_size"]), L=len(layers),
                Ne=int(cfg["num_experts"]), K=int(cfg["num_experts_per_tok"]),
                first=first, held=held,
                route_norm=bool(cfg["norm_topk_prob"]),
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                kl_weight=float(cfg.get("indexer_loss_weight", 1.0)),
                embed_scale=bool(cfg.get("embedding_rows_times_sqrt_hidden")))


# ----------------------------------------------------- the program's model

def _make_model(cfg: Mapping[str, Any], seq: int, interpret: bool,
                dtype: Any = jnp.bfloat16) -> Any:
    """The program's model at the configuration's sizes: ``Transformer``
    with the learned sparse attention (its kernels interpreted with
    ``interpret``) and the routed expert layer over its share. No remat: a
    layer's loss is sown out of it. ``dtype`` is the tests'."""
    from torchft_tpu.models import Transformer
    from torchft_tpu.models.transformer import TransformerConfig

    w = _w(cfg)
    tcfg = TransformerConfig(
        vocab_size=w["V"], num_layers=w["L"], embed_dim=w["E"],
        num_heads=w["H"], num_kv_heads=w["G"], attn_head_dim=w["D"],
        max_seq_len=seq, rope_theta=w["theta"], rms_norm_eps=w["eps"],
        qk_norm=True, embed_scale=w["embed_scale"], dtype=dtype,
        sparse_topk=w["topk"],
        indexer_heads=w["J"], indexer_head_dim=w["C"],
        sparse_interpret=interpret,
        moe_experts=w["Ne"], moe_top_k=w["K"], moe_dispatch="routed",
        moe_dim=w["Fm"], moe_held=(w["first"], w["held"]),
        moe_score="softmax", moe_route_norm=w["route_norm"],
        moe_route_scale=1.0, moe_interpret=interpret)
    return Transformer(tcfg)


def make_loss_fn(cfg: Mapping[str, Any], seq: int, interpret: bool,
                 **model_kw: Any) -> Callable:
    """The program's loss: the model above under ``sparse_lm_loss``."""
    model = _make_model(cfg, seq, interpret, **model_kw)
    w = _w(cfg)

    def loss_fn(params, batch):
        return sparse_lm_loss(model, params, batch["tokens"], w["kl_weight"])

    return loss_fn


def program_selections(cfg: Mapping[str, Any], seq: int, interpret: bool
                       ) -> Callable:
    """``(params, tokens) -> [experts [T, K] of each expert layer]``: what
    the program's routers select (``benchmarks/route_flips.py``)."""
    model = _make_model(cfg, seq, interpret)
    w = _w(cfg)

    def selections(params, tokens):
        _, state = model.apply(params, tokens, return_hidden=True,
                               mutable=["intermediates"])
        return [state["intermediates"][f"layer_{i}"]["moe"]["experts"][0]
                for i in range(w["L"])]

    return selections


def program_key_selections(cfg: Mapping[str, Any], seq: int, interpret: bool,
                           dtype: Any = jnp.bfloat16) -> Callable:
    """``(params, tokens) -> [selection [B, S, S] int8 of each layer]``: the
    keys the program's indexers select."""
    model = _make_model(cfg, seq, interpret, dtype=dtype)
    w = _w(cfg)

    def selections(params, tokens):
        _, state = model.apply(params, tokens, return_hidden=True,
                               mutable=["intermediates"])
        return [state["intermediates"][f"layer_{i}"]["attn"]["selection"][0]
                for i in range(w["L"])]

    return selections


# ------------------------------------------------------------- the shapes

def param_shapes(cfg: Mapping[str, Any]) -> Dict[str, Any]:
    """The parameter tree's shapes (all float32), named as the program's
    ``Transformer`` names them. One-dimensional leaves (norm gains, the key
    norm's bias) are made as ones, the others normal(0,
    initializer_range)."""
    w = _w(cfg)
    E, H, G, D = w["E"], w["H"], w["G"], w["D"]
    indexer = {"a": {"kernel": (E, w["J"], w["C"])},
               "b": {"kernel": (E, w["C"])},
               "b_norm": {"scale": (w["C"],), "bias": (w["C"],)},
               "u": {"kernel": (E, w["J"])}}
    attn = {"q": {"kernel": (E, H, D)}, "k": {"kernel": (E, G, D)},
            "v": {"kernel": (E, G, D)}, "q_norm": {"scale": (D,)},
            "k_norm": {"scale": (D,)}, "o": {"kernel": (H * D, E)},
            "indexer": indexer}
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


def _layer_norm(x, scale, bias, eps, r):
    x = r(x)
    c = r(x - jnp.mean(x, axis=-1, keepdims=True))
    var = r(jnp.mean(r(c * c), axis=-1, keepdims=True))
    return r(r(c * r(jax.lax.rsqrt(var + eps))) * scale + bias)


def _rope(x, theta):
    """x: [B, S, H, D]; pairs (i, i + D/2) turn by position *
    theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def index_scores(g, ix, w: Mapping[str, Any], r) -> Any:
    """``I`` [B, S, S] of the stopped stream ``g`` [B, S, E] (every pair;
    the caller masks the causal ones)."""
    mm, nrm, ind = (r.get("matmul", _same), r.get("norm", _same),
                    r.get("indexer", _same))
    a = _rope(jnp.einsum("bse,ejc->bsjc", mm(g), mm(ix["a"]["kernel"])),
              w["theta"])
    b = _layer_norm(mm(g) @ mm(ix["b"]["kernel"]), ix["b_norm"]["scale"],
                    ix["b_norm"]["bias"], w["eps"], nrm)
    b = _rope(b[:, :, None, :], w["theta"])[:, :, 0]
    u = mm(g) @ mm(ix["u"]["kernel"])
    scale = (w["J"] * w["C"]) ** -0.5

    def one_head(total, xs):
        a_j, u_j = xs            # [B, S, C], [B, S]
        z = ind(jnp.maximum(jnp.einsum("btc,bsc->bts", mm(a_j), mm(b)), 0.0))
        return ind(total + ind(u_j[..., None] * z)), None

    total, _ = jax.lax.scan(
        jax.checkpoint(one_head),
        jnp.zeros(g.shape[:2] + g.shape[1:2], jnp.float32),
        (jnp.moveaxis(a, 2, 0), jnp.moveaxis(u, 2, 0)))
    return ind(total * scale)


def reference_selection(scores, topk: int) -> Any:
    """The boolean ``[B, S, S]`` mask of each row's ``min(t + 1, topk)``
    largest causal scores, by ``jax.lax.top_k`` a row."""
    B, S, _ = scores.shape
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                           min(topk, S))
    rows = jnp.arange(S)[None, :, None]
    mask = jnp.zeros((B, S, S), bool).at[
        jnp.arange(B)[:, None, None], rows, idx].set(True)
    return jnp.logical_and(mask, causal)


def _attention(q, k, v, mask, mm, soft):
    """Softmax attention under ``mask`` [B, S, S], q [B,S,H,D], k/v
    [B,S,G,D], ``HEAD_BLOCK`` query heads of one group at a time. Returns
    the output [B, S, H*D] and, in a second pass that is not differentiated
    (so that no block's probabilities are kept for a backward), the mean
    over heads of the probabilities [B, S, S]."""
    B, S, H, D = q.shape
    G = k.shape[2]
    hb = min(HEAD_BLOCK, H // G)
    n = H // hb                                   # blocks, group-major
    qb = q.transpose(2, 0, 1, 3).reshape(n, hb, B, S, D)
    kb = jnp.repeat(k.transpose(2, 0, 1, 3), n // G, axis=0)
    vb = jnp.repeat(v.transpose(2, 0, 1, 3), n // G, axis=0)

    def probabilities(q1, k1):                   # [hb,B,S,D], [B,S,D]
        s = soft(jnp.einsum("hbqd,bkd->hbqk", mm(q1), mm(k1)) * (D ** -0.5))
        return soft(jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf),
                                   axis=-1))

    @jax.checkpoint
    def block(xs):
        q1, k1, v1 = xs
        return jnp.einsum("hbqk,bkd->hbqd", mm(probabilities(q1, k1)),
                          mm(v1))

    out = jax.lax.map(block, (qb, kb, vb))
    out = out.reshape(H, B, S, D).transpose(1, 2, 0, 3).reshape(B, S, H * D)
    p_sum, _ = jax.lax.scan(
        lambda total, xs: (total + jnp.sum(probabilities(*xs), axis=0), None),
        jnp.zeros((B, S, S), jnp.float32),
        jax.lax.stop_gradient((qb, kb)))
    return out, p_sum / H


def _mixer(h, a, w, r, collect):
    """``(the attention block's output, L_I)``."""
    mm, nrm = r.get("matmul", _same), r.get("norm", _same)
    q = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["q"]["kernel"]))
    k = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["k"]["kernel"]))
    v = jnp.einsum("bse,ehd->bshd", mm(h), mm(a["v"]["kernel"]))
    q = _rope(_rms_norm(q, a["q_norm"]["scale"], w["eps"], nrm), w["theta"])
    k = _rope(_rms_norm(k, a["k_norm"]["scale"], w["eps"], nrm), w["theta"])
    scores = index_scores(jax.lax.stop_gradient(h), a["indexer"], w, r)
    S = h.shape[1]
    topk = w["topk"] // 2 if "half_topk" in r else w["topk"]
    mask = reference_selection(jax.lax.stop_gradient(scores), topk)
    if collect is not None:
        collect["keys"].append(mask)
    seen = (jnp.broadcast_to(jnp.arange(S)[:, None] >= jnp.arange(S)[None],
                             mask.shape)
            if "dense_attention" in r else mask)
    o, p = _attention(q, k, v, seen, mm, r.get("softmax", _same))
    # the loss on the set the attention saw: p sums to one over it
    log_r = jax.nn.log_softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    kl = jnp.where(seen, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                              - jnp.where(seen, log_r, 0.0)), 0.0)
    kl = jnp.sum(kl) / (h.shape[0] * S)
    return mm(o) @ mm(a["o"]["kernel"]), kl


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
    them (a ``scan`` over the stacks, so that the program holds one expert's
    code and not ``held`` copies), each computing every token under a mask
    of the pairs routed to it."""
    weights, idx = reference_routing(u, p["router"]["kernel"], w, rt)
    if collect is not None:
        collect["experts"].append(idx.reshape(-1, idx.shape[-1]))

    @jax.checkpoint
    def one(e, gate, up, down):
        w_e = jnp.sum(jnp.where(idx == w["first"] + e, weights, 0.0), axis=-1)
        return w_e[..., None] * _swiglu(u, gate, up, down, mm)

    m, _ = jax.lax.scan(
        lambda m, xs: (m + one(*xs), None), jnp.zeros_like(u),
        (jnp.arange(w["held"]), p["wi_gate"], p["wi_up"], p["wo"]))
    return m


def _one_layer(x, lp, w, r, collect):
    mm, res = r.get("matmul", _same), r.get("residual", _same)
    nrm = r.get("norm", _same)
    h = _rms_norm(x, lp["attn_norm"]["scale"], w["eps"], nrm)
    a, kl = _mixer(h, lp["attn"], w, r, collect)
    x = res(x + a)
    u = _rms_norm(x, lp["mlp_norm"]["scale"], w["eps"], nrm)
    return res(x + _experts(u, lp["moe"], w, mm, r.get("router", _same),
                            collect)), kl


def _layer(x, lp, w, r, collect):
    """One layer; without ``collect`` recomputed in the backward, so that
    four layers' float32 intermediates at 8192 tokens fit beside the tree
    and its gradients."""
    if collect is None:
        return jax.checkpoint(
            lambda x_, lp_: _one_layer(x_, lp_, w, r, None))(x, lp)
    return _one_layer(x, lp, w, r, collect)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _mean_nll(states, head, targets, mm, lg):
    logits = lg(mm(states) @ mm(head))
    logp = lg(jax.nn.log_softmax(logits, axis=-1))
    return jnp.mean(-jnp.take_along_axis(logp, targets[..., None], axis=-1))


def reference_losses(params: Any, tokens: Any, cfg: Mapping[str, Any],
                     rounding: Optional[Mapping[str, Callable]] = None,
                     collect: Optional[Dict[str, List[Any]]] = None
                     ) -> Tuple[Any, List[Any]]:
    """``(L_lm, [L_I of each layer])`` in float32 at the highest matmul
    precision (:func:`reference_loss` says what ``rounding`` is)."""
    w = _w(cfg)
    r = dict(rounding or {})
    mm, res = r.get("matmul", _same), r.get("residual", _same)
    p = params["params"]
    kls = []
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][tokens]
        if w["embed_scale"]:
            x = x * (w["E"] ** 0.5)
        x = res(x)
        for i in range(w["L"]):
            x, kl = _layer(x, p[f"layer_{i}"], w, r, collect)
            kls.append(kl)
        x = _rms_norm(x, p["final_norm"]["scale"], w["eps"],
                      r.get("norm", _same))
        return _mean_nll(x[:, :-1], p["lm_head"]["kernel"], tokens[:, 1:],
                         mm, r.get("logits", _same)), kls


def reference_loss(params: Any, tokens: Any, cfg: Mapping[str, Any],
                   rounding: Optional[Mapping[str, Callable]] = None,
                   collect: Optional[Dict[str, List[Any]]] = None) -> Any:
    """``L_lm + indexer_loss_weight * sum L_I`` of ``tokens`` [B, S].
    ``rounding`` maps a site to a function put on every value there:
    ``matmul`` (the inputs of every matrix product but the router's; the
    index products' among them), ``router`` (its inputs and scores),
    ``indexer`` (the index scores' ReLU, weighting and sum), ``residual``,
    ``norm``, ``softmax``, ``logits``, and the switches ``dense_attention``,
    ``drop_indexer_loss`` and ``half_topk`` (the module docstring). A site
    that is not named is left in float32."""
    lm, kls = reference_losses(params, tokens, cfg, rounding, collect)
    if "drop_indexer_loss" in (rounding or {}):
        return lm
    return lm + _w(cfg)["kl_weight"] * sum(kls)


def _collected(params, tokens, cfg, rounding) -> Dict[str, List[Any]]:
    collect: Dict[str, List[Any]] = {"experts": [], "keys": []}
    reference_loss(params, tokens, cfg, rounding, collect=collect)
    return collect


def reference_selections(params: Any, tokens: Any, cfg: Mapping[str, Any],
                         rounding: Optional[Mapping[str, Callable]] = None
                         ) -> List[Any]:
    """``[experts [T, K] of each expert layer]`` as the reference selects
    them."""
    return _collected(params, tokens, cfg, rounding)["experts"]


def reference_key_selections(params: Any, tokens: Any,
                             cfg: Mapping[str, Any],
                             rounding: Optional[Mapping[str, Callable]] = None
                             ) -> List[Any]:
    """``[keys [B, S, S] bool of each layer]`` as the reference selects
    them."""
    return _collected(params, tokens, cfg, rounding)["keys"]


# ------------------------------------------- what the algorithm needs
#
# Needed work only (``dense_gqa_decoder.py`` says what that leaves out): of
# the attention the SELECTED pairs, ``sum_t min(t + 1, topk)`` a head; of
# the index scores the causal triangle; of the routed experts the expected
# ``top_k * held / num_experts`` a token; no recomputation (the loss's
# second pass over ``q k^T`` is work the implementation chose).

def selected_pairs(seq: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)`` over a sequence's queries."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def param_count(cfg: Mapping[str, Any]) -> int:
    """Parameters of the decoder as run, from the configuration alone."""
    w = _w(cfg)
    E = w["E"]
    HD = w["H"] * w["D"]
    attn = 2 * E * HD + 2 * E * w["G"] * w["D"] + 2 * w["D"]
    indexer = E * w["J"] * w["C"] + E * w["C"] + 2 * w["C"] + E * w["J"]
    experts = E * w["Ne"] + w["held"] * 3 * E * w["Fm"]
    return (w["L"] * (attn + indexer + experts + 2 * E)
            + 2 * w["V"] * E + E)


def layer_forward_flops(cfg: Mapping[str, Any], seq: int
                        ) -> List[Dict[str, float]]:
    """Forward operations for one token, layer by layer and part by part."""
    w = _w(cfg)
    E, HD = w["E"], w["H"] * w["D"]
    layer = {
        "proj": 2.0 * E * (2 * HD + 2 * w["G"] * w["D"]),
        "attn": 2 * 2.0 * w["D"] * w["H"] * selected_pairs(seq, w["topk"])
        / seq,
        "indexer_proj": 2.0 * E * (w["J"] * w["C"] + w["C"] + w["J"]),
        "index_scores": 2.0 * w["J"] * w["C"] * (seq + 1) / 2,
        "router": 2.0 * E * w["Ne"],
        "routed": (w["K"] * w["held"] / w["Ne"]) * 3 * 2.0 * E * w["Fm"]}
    return [dict(layer) for _ in range(w["L"])]


def forward_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    w = _w(cfg)
    return sum(sum(p.values()) for p in layer_forward_flops(cfg, seq)) \
        + 2.0 * w["E"] * w["V"]


def train_flops_per_token(cfg: Mapping[str, Any], seq: int) -> float:
    """Forward and backward: the backward of a matmul is two matmuls."""
    return 3.0 * forward_flops_per_token(cfg, seq)
