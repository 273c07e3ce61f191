"""Plain reference: the GLM-5.2 decoder (``model_type glm_moe_dsa``) in
float32 jax.numpy - one full-sequence forward without cache, kernels or
batching.

The published description: zai-org/GLM-5.2's config.json. Its layers
are DeepSeek's, at GLM's widths: multi-head latent attention (DeepSeek-
V2, arXiv:2405.04434), DeepSeek-V3.2's sparse attention (a lightning
indexer and a top-k) with the indexer on the layers ``indexer_types``
marks ``full`` and its selection reused on those marked ``shared``
(IndexShare), and DeepSeek-V3's ``noaux_tc`` router (arXiv:2412.19437).
h is the residual stream; every norm is RMSNorm(eps ``rms_norm_eps``)
but the indexer's LayerNorm; no bias but that LayerNorm's and the
router's correction bias.

    a    = RMSNorm(h)
    c_q  = RMSNorm(Wqa a);  q = Wqb c_q -> heads x [q_n 192 ; q_r 64]
    [c_kv ; k_r] = Wkva a;  c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r), one
           for all heads;  q_r = RoPE(q_r);  interleaved pairs
           (2i, 2i+1), theta ``rope_theta``, over the 64 rotary dims
    [k_n ; v] = Wkvb c_kv  -> heads x [192 ; 256]
    s_tj = (q_n[t] . k_n[j] + q_r[t] . k_r[j]) / sqrt(256)
    o_t  = sum_{j in S_t} softmax_j(s_tj) v_j;   h += Wo o
  indexer, ``full`` layers:
    q^I = WIq c_q -> 32 heads x 128;  k^I = LayerNorm(WIk a) -> 128, one
           for all heads;  RoPE (interleaved pairs) on the FIRST 64 of
           the 128;  w = WIw a -> 32
    I_tj = sum_h w_th * 32**-0.5 * 128**-0.5 * relu(q^I_th . k^I_j), j <= t
    S_t  = the ``index_topk`` positions j <= t of largest I_tj (all of
           them while t < index_topk): dense scores for every pair, a
           sort, the first k
  ``shared`` layers: S_t is the set the nearest earlier ``full`` layer
           chose for the same query
  feed-forward, a = RMSNorm(h):
    layers before ``first_k_dense_replace``: h += Wd(silu(Wg a) * Wu a)
    the others: sc = sigmoid(Wr a) over all ``n_routed_experts``; the
           ``num_experts_per_tok`` largest of sc + b are chosen; g_e =
           sc_e / sum_chosen(sc) * ``routed_scaling_factor``;
           h += sum_chosen g_e E_e(a) + E_shared(a), every expert a
           gated SiLU. A loop over the experts HELD here
           (``n_routed_experts_held``, the first of them
           ``held_first``): what an absent expert would add is left
           out, here as in the program - the chip's share of a layer
           that 16 chips divide (guide model-configs, section 4).
    logits = Whead RMSNorm(h) over the rows of the vocabulary held here

Readings the catalog's config does not carry (each also under the
configuration's ``assumed``):
  * the indexer's LayerNorm on k^I and its two scale factors, and the
    rotary on the first 64 of its 128 dimensions, are DeepSeek-V3.2's
    published inference code;
  * V3.2's Hadamard rotation of q^I and k^I and their float8
    quantisation are left out: an orthogonal rotation changes no dot
    product, and the quantisation is a storage format of that kernel;
  * IndexShare as reuse of the chosen positions (not of the scores);
  * the correction bias b is a small normal draw, the norm gains 1;
  * the multi-token-prediction layer (``num_nextn_predict_layers`` 1)
    is a drafter beside the model and is not part of this forward.
The rotary pairs are rotated in place. The published code permutes the
pairs to the half-split order first and leaves them there, the same
permutation on q and k: no dot product differs.

Departures in parameter LAYOUT only, following models/transformer.py
and ops/moe.py (the program under test): gate and up of the dense
feed-forward are the two row blocks of ``*_ffn_gate_up_weight``; the
held experts are stacked on a leading axis and stored transposed,
K-major, as are the shared expert's three matrices; ``kv_b_proj`` is an
input of the attention op (``*_attn_kv_b_weight``), as is the gain of
c_kv's norm.

``select=False`` is the control "the same reference with the selection
left out": attention over all j <= t. ``round_to=`` rounds every matmul
operand (weights and activations) to that dtype first. ``tail=n``
returns the logits of the last n positions alone. ``return_sets`` adds
the chosen sets as a bool (full layers, B, T, T). Queries are taken
``block`` at a time, so that 4,112 positions fit beside a live engine.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, gamma, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(gamma)


def _layer_norm(x, gamma, beta, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(gamma) + _f32(beta)


def _rope(x, theta):
    """x (B, T, ..., d): rotate the pair (2i, 2i+1) of position t by
    t * theta**(-2i/d)."""
    d, T = x.shape[-1], x.shape[1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # (T, d/2)
    ang = ang.reshape((1, T) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pair = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pair[..., 0], pair[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _rounder(round_to):
    if round_to is None:
        return lambda x: x
    return lambda x: x.astype(round_to).astype(jnp.float32)


def _by_query_block(fn, T, block, *per_query):
    """``fn(t0, *blocks)`` over blocks of ``block`` queries (arrays
    whose axis 1 is the query axis), stitched back along axis 1."""
    n = -(-T // block)
    pad = n * block - T

    def cut(x):
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((x.shape[0], n, block) + x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    out = jax.lax.map(lambda a: fn(a[0], *a[1:]),
                      (jnp.arange(n) * block,) + tuple(
                          cut(x) for x in per_query))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape((out.shape[0], n * block) + out.shape[3:])[:, :T]


def index_sets(c_q, a, p, params, cfg, rd, block):
    """bool (B, T, T): S_t of every query, by dense scores and a
    sort."""
    B, T, _ = a.shape
    Hi, d = cfg["index_n_heads"], cfg["index_head_dim"]
    dr, topk = cfg["qk_rope_head_dim"], cfg["index_topk"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    q = (rd(c_q) @ rd(_f32(params[f"{p}_idx_q_weight"])).T) \
        .reshape(B, T, Hi, d)
    k = _layer_norm(rd(a) @ rd(_f32(params[f"{p}_idx_k_weight"])).T,
                    params[f"{p}_idx_k_norm_gamma"],
                    params[f"{p}_idx_k_norm_beta"])
    q = jnp.concatenate([_rope(q[..., :dr], theta), q[..., dr:]], axis=-1)
    k = jnp.concatenate([_rope(k[..., :dr], theta), k[..., dr:]], axis=-1)
    w = (rd(a) @ rd(_f32(params[f"{p}_idx_w_weight"])).T) \
        * (Hi ** -0.5 * d ** -0.5)
    keys = jnp.arange(T)

    def rows(t0, q_blk, w_blk):
        dots = jnp.einsum("bqhd,bkd->bqhk", rd(q_blk), rd(k))
        score = jnp.einsum("bqhk,bqh->bqk", jnp.maximum(dots, 0.0), w_blk)
        t = t0 + jnp.arange(q_blk.shape[1])
        causal = keys[None, None, :] <= t[None, :, None]
        score = jnp.where(causal, score, -jnp.inf)
        order = jnp.argsort(-score, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1)
        return (rank < topk) & causal

    return _by_query_block(rows, T, block, q, w)


def attention(a, c_q, sets, p, params, cfg, rd, block):
    """The un-absorbed MLA of rows ``a`` (B, T, D) over exactly
    ``sets``: (B, T, H * v_head_dim)."""
    B, T, _ = a.shape
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    eps = cfg["rms_norm_eps"]
    q = (rd(c_q) @ rd(_f32(params[f"{p}_q_b_weight"])).T) \
        .reshape(B, T, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], theta)
    kv = rd(a) @ rd(_f32(params[f"{p}_kv_a_weight"])).T
    c_kv = _rms_norm(kv[..., :rank], params[f"{p}_attn_kv_norm_weight"],
                     eps)
    k_r = _rope(kv[..., rank:], theta)                         # (B, T, dr)
    kvb = (rd(c_kv) @ rd(_f32(params[f"{p}_attn_kv_b_weight"])).T) \
        .reshape(B, T, H, dn + dv)
    k_n, v = kvb[..., :dn], kvb[..., dn:]
    scale = float(dn + dr) ** -0.5

    def rows(t0, qn_blk, qr_blk, set_blk):
        s = (jnp.einsum("bqhn,bkhn->bhqk", rd(qn_blk), rd(k_n))
             + jnp.einsum("bqhr,bkr->bhqk", rd(qr_blk), rd(k_r))) * scale
        s = jnp.where(set_blk[:, None], s, -jnp.inf)
        # a padded query past T has an empty set: its row is dropped
        prob = jnp.where(set_blk[:, None], jax.nn.softmax(s, axis=-1), 0.0)
        return jnp.einsum("bhqk,bkhv->bqhv", rd(prob), rd(v))

    out = _by_query_block(rows, T, block, q_n, q_r, sets)
    return out.reshape(B, T, H * dv)


def _gated(m, gate, up, down, rd):
    h = jax.nn.silu(rd(m) @ rd(_f32(gate))) * (rd(m) @ rd(_f32(up)))
    return rd(h) @ rd(_f32(down))


def expert_layer(m, p, params, cfg, rd, held=None):
    """The sparse feed-forward of rows ``m`` (N, D): the held experts'
    part (``held`` = (first, count), default the configuration's) of
    every row's weighted sum, one expert at a time, and the shared
    expert: ``(routed (N, D), shared (N, D), chosen (N, k))``."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    first, count = held or (cfg.get("held_first", 0),
                            cfg.get("n_routed_experts_held", E))
    sc = jax.nn.sigmoid(rd(m) @ rd(_f32(params[f"{p}_moe_router_weight"])).T)
    _, chosen = jax.lax.top_k(sc + _f32(params[f"{p}_moe_router_bias"]), k)
    picked = jnp.any(chosen[:, :, None] == jnp.arange(E)[None, None, :],
                     axis=1)
    weight = jnp.where(picked, sc, 0.0)
    if cfg["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * cfg["routed_scaling_factor"]

    def one_expert(acc, xs):
        gate, up, down, w = xs
        return acc + w[:, None] * _gated(m, gate, up, down, rd), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (params[f"{p}_moe_gate_weight"], params[f"{p}_moe_up_weight"],
         params[f"{p}_moe_down_weight"], weight.T[first:first + count]))
    shared = _gated(m, params[f"{p}_moe_shared_gate_weight"],
                    params[f"{p}_moe_shared_up_weight"],
                    params[f"{p}_moe_shared_down_weight"], rd)
    return routed, shared, chosen.astype(jnp.int32)


def forward(params, tokens, config, name="lm", round_to=None, select=True,
            tail=None, return_sets=False, block=128):
    """Logits (B, T, vocab held) of ``tokens`` (B, T) int32 under
    ``params`` ({program name: array}), float32 at the highest matmul
    precision (module docstring for the switches)."""
    eps = config["rms_norm_eps"]
    D = config["hidden_size"]
    B, T = tokens.shape
    rd = _rounder(round_to)
    block = min(block, T)
    causal = jnp.tril(jnp.ones((T, T), bool))[None]
    chosen_sets = []
    with jax.default_matmul_precision("highest"):
        x = _f32(params[f"{name}_tok_embed_weight"])[tokens]
        sets = None
        for i, kind in enumerate(config["indexer_types"]):
            p = f"{name}_l{i}"
            a = _rms_norm(x, params[f"{p}_ln1_gamma"], eps)
            c_q = _rms_norm(rd(a) @ rd(_f32(params[f"{p}_q_a_weight"])).T,
                            params[f"{p}_q_a_norm_gamma"], eps)
            if kind == "full":
                sets = index_sets(c_q, a, p, params, config, rd, block)
                chosen_sets.append(sets)
            att = attention(a, c_q, jnp.broadcast_to(
                sets if select else causal, (B, T, T)), p, params, config,
                rd, block)
            x = x + rd(att) @ rd(_f32(params[f"{p}_proj_weight"])).T
            m = _rms_norm(x, params[f"{p}_ln2_gamma"], eps)
            if i < config["first_k_dense_replace"]:
                F = config["intermediate_size"]
                w = _f32(params[f"{p}_ffn_gate_up_weight"])
                x = x + _gated(m, w[:F].T, w[F:].T,
                               _f32(params[f"{p}_ffn_down_weight"]).T, rd)
            else:
                routed, shared, _ = expert_layer(
                    m.reshape(B * T, D), p, params, config, rd)
                x = x + (routed + shared).reshape(B, T, D)
        if tail is not None:
            x = x[:, T - tail:]
        x = _rms_norm(x, params[f"{name}_ln_f_gamma"], eps)
        logits = rd(x) @ rd(_f32(params[f"{name}_head_weight"])).T
    if return_sets:
        return logits, jnp.stack(chosen_sets)
    return logits


def set_flip_share(ours, theirs):
    """Share of (full layer, sequence, query) selections in which the
    two sides chose different sets of positions."""
    return jnp.mean(jnp.any(jnp.asarray(ours) != jnp.asarray(theirs),
                            axis=-1).astype(jnp.float32))
