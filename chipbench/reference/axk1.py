"""Plain reference: the A.X-K1 decoder (``model_type axk1``) in float32
jax.numpy - one full-sequence forward without cache, kernels or
batching.

The published description: skt/A.X-K1's config.json. Its layers are the
DeepSeek-V3 family's (arXiv:2412.19437) at A.X-K1's widths: multi-head
latent attention (arXiv:2405.04434) over EVERY earlier position - no
indexer, no selection -, its rotary under YaRN (arXiv:2309.00071), and a
sigmoid router that chooses inside the best groups of experts, beside
one shared expert. h is the residual stream; every norm is
RMSNorm(eps ``rms_norm_eps``); no bias anywhere; untied head.

    a    = RMSNorm(h)
    c_q  = RMSNorm(Wqa a);  q = Wqb c_q -> heads x [q_n 128 ; q_r 64]
    [c_kv ; k_r] = Wkva a;  c_kv = RMSNorm(c_kv);  k_r = R(k_r), one for
           all heads;  q_r = R(q_r)
    [k_n ; v] = Wkvb c_kv  -> heads x [128 ; 128]
    s_tj = s * (q_n[t] . k_n[j] + q_r[t] . k_r[j])      all j <= t
    o_t  = sum_j softmax_j(s_tj) v_j;   h += Wo o
  YaRN (``rope_scaling``: factor f, original positions P, beta_fast,
  beta_slow, mscale, mscale_all_dim), d = ``qk_rope_head_dim``:
    theta_i = rope_theta ** (-2i / d),  i = 0 .. d/2 - 1
    turns(b) = d ln(P / (2 pi b)) / (2 ln rope_theta)
    low = floor(turns(beta_fast)), high = ceil(turns(beta_slow)),
           clipped to [0, d - 1];  r_i = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = theta_i (1 - r_i) + theta_i / f * r_i
    R rotates the pair (2i, 2i+1) of position t by t * inv_freq_i, cos
           and sin times m(mscale) / m(mscale_all_dim), where
           m(a) = 0.1 a ln f + 1
    s = (128 + 64) ** -0.5 * m(mscale_all_dim) ** 2
  feed-forward, a = RMSNorm(h):
    layers before ``first_k_dense_replace``: h += Wd(silu(Wg a) * Wu a)
    the others: sc = sigmoid(Wr a) over all ``n_routed_experts``, in
           ``n_group`` groups of consecutive experts; a group's score is
           the sum of its two largest sc; the ``topk_group`` groups of
           largest score are kept (ties: the lowest index); among their
           experts the ``num_experts_per_tok`` of largest sc are chosen
           (ties: the lowest index); g_e = sc_e / (sum_chosen(sc) +
           1e-20) * ``routed_scaling_factor``;
           h += sum_chosen g_e E_e(a) + E_shared(a), every expert a
           gated SiLU. A loop over the experts HELD here
           (``n_routed_experts_held``, the first of them
           ``held_first``): what an absent expert would add is left
           out, here as in the program - the chip's share of a layer
           that 16 chips divide (guide model-configs, section 4).
    logits = Whead RMSNorm(h) over the rows of the vocabulary held here

Readings the catalog's config leaves open (each also under the
configuration's ``assumed``):
  * ``topk_method: "none"`` is read as "no correction bias in the
    choice" (``seq_aux: true``: an auxiliary-loss router), with
    ``n_group`` 8 and ``topk_group`` 4 applied as the family applies
    them - a router that ignored them would not publish them;
  * the rotary turns adjacent pairs (2i, 2i+1), the family's layout
    (``rope_interleave``); with seeded weights any other pairing is a
    fixed permutation of Wqb's and Wkva's rotary columns;
  * the norm gains are 1.

Departures in parameter LAYOUT only, following models/transformer.py
and ops/moe.py (the program under test): gate and up of the dense
feed-forward are the two row blocks of ``*_ffn_gate_up_weight``; the
held experts are stacked on a leading axis and stored transposed,
K-major, as are the shared expert's three matrices; ``kv_b_proj`` is an
input of the attention op (``*_attn_kv_b_weight``), as is the gain of
c_kv's norm.

Controls: ``round_to=`` rounds every matmul operand (weights and
activations) to that dtype first; ``yarn="no_scale"`` leaves YaRN's
factor of the softmax scale out, ``yarn="plain"`` turns the pairs by
theta_i alone as well (the rotary of a model without ``rope_scaling``).
``tail=n`` returns the logits of the last n positions alone. Queries are
taken ``block`` at a time, so that 4,112 positions fit beside a live
engine.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, gamma, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(gamma)


def _m(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1.0 and a else 1.0


def yarn(cfg, mode="yarn"):
    """``(inv_freq (d/2,) float64, trig scale, softmax scale, (low,
    high))`` of the module docstring's YaRN under ``cfg``; ``mode``:
    the controls."""
    d = cfg["qk_rope_head_dim"]
    base = float(cfg["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    theta = base ** (-2.0 * i / d)
    plain = float(cfg["qk_nope_head_dim"] + d) ** -0.5
    sc = cfg.get("rope_scaling")
    if not sc or mode == "plain":
        return theta, 1.0, plain, None
    f, P = float(sc["factor"]), float(sc["original_max_position_embeddings"])

    def turns(beta):
        return d * math.log(P / (2.0 * math.pi * beta)) \
            / (2.0 * math.log(base))

    low = max(math.floor(turns(float(sc["beta_fast"]))), 0)
    high = min(math.ceil(turns(float(sc["beta_slow"]))), d - 1)
    r = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = theta * (1.0 - r) + theta / f * r
    m_all = _m(f, float(sc.get("mscale_all_dim", 0)))
    trig = _m(f, float(sc.get("mscale", 1))) / m_all
    scale = plain if mode == "no_scale" else plain * m_all ** 2
    return inv, trig, scale, (low, high)


def _rope(x, inv_freq, trig):
    """x (B, T, ..., d): rotate the pair (2i, 2i+1) of position t by
    t * inv_freq[i]."""
    d, T = x.shape[-1], x.shape[1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)                   # (T, d/2)
    ang = ang.reshape((1, T) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang) * trig, jnp.sin(ang) * trig
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


def attention(a, c_q, p, params, cfg, rd, block, mode="yarn"):
    """The un-absorbed MLA of rows ``a`` (B, T, D) over every j <= t:
    (B, T, H * v_head_dim)."""
    B, T, _ = a.shape
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    inv_freq, trig, scale, _ = yarn(cfg, mode)
    q = (rd(c_q) @ rd(_f32(params[f"{p}_q_b_weight"])).T) \
        .reshape(B, T, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], inv_freq, trig)
    kv = rd(a) @ rd(_f32(params[f"{p}_kv_a_weight"])).T
    c_kv = _rms_norm(kv[..., :rank], params[f"{p}_attn_kv_norm_weight"],
                     eps)
    k_r = _rope(kv[..., rank:], inv_freq, trig)                # (B, T, dr)
    kvb = (rd(c_kv) @ rd(_f32(params[f"{p}_attn_kv_b_weight"])).T) \
        .reshape(B, T, H, dn + dv)
    k_n, v = kvb[..., :dn], kvb[..., dn:]
    keys = jnp.arange(T)

    def rows(t0, qn_blk, qr_blk):
        s = (jnp.einsum("bqhn,bkhn->bhqk", rd(qn_blk), rd(k_n))
             + jnp.einsum("bqhr,bkr->bhqk", rd(qr_blk), rd(k_r))) * scale
        t = t0 + jnp.arange(qn_blk.shape[1])
        seen = keys[None, :] <= t[:, None]                     # (q, T)
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhv->bqhv", rd(prob), rd(v))

    out = _by_query_block(rows, T, block, q_n, q_r)
    return out.reshape(B, T, H * dv)


def _gated(m, gate, up, down, rd):
    h = jax.nn.silu(rd(m) @ rd(_f32(gate))) * (rd(m) @ rd(_f32(up)))
    return rd(h) @ rd(_f32(down))


def route(sc, cfg):
    """The group-limited choice over scores ``sc`` (N, E): ``(chosen
    (N, k) int32, largest sc first; weight (N, E) float32, 0 off the
    chosen)``. Sorts, stable, so that a tie goes to the lowest index."""
    N, E = sc.shape
    k, G = cfg["num_experts_per_tok"], cfg["n_group"]
    keep = cfg["topk_group"]
    groups = sc.reshape(N, G, E // G)
    score = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)   # (N, G)
    place = jnp.argsort(jnp.argsort(-score, axis=-1, stable=True), axis=-1)
    kept = jnp.repeat(place < keep, E // G, axis=-1)               # (N, E)
    order = jnp.argsort(jnp.where(kept, -sc, jnp.inf), axis=-1, stable=True)
    chosen = order[:, :k]
    picked = jnp.any(chosen[:, :, None] == jnp.arange(E)[None, None, :],
                     axis=1)
    weight = jnp.where(picked, sc, 0.0)
    if cfg["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weight * cfg["routed_scaling_factor"]


def expert_layer(m, p, params, cfg, rd, held=None):
    """The sparse feed-forward of rows ``m`` (N, D): the held experts'
    part (``held`` = (first, count), default the configuration's) of
    every row's weighted sum, one expert at a time, and the shared
    expert: ``(routed (N, D), shared (N, D), chosen (N, k))``."""
    E = cfg["n_routed_experts"]
    first, count = held or (cfg.get("held_first", 0),
                            cfg.get("n_routed_experts_held", E))
    sc = jax.nn.sigmoid(rd(m) @ rd(_f32(params[f"{p}_moe_router_weight"])).T)
    chosen, weight = route(sc, cfg)

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
    return routed, shared, chosen


def forward(params, tokens, config, name="lm", round_to=None, tail=None,
            block=128, yarn="yarn", return_chosen=False):
    """Logits (B, T, vocab held) of ``tokens`` (B, T) int32 under
    ``params`` ({program name: array}), float32 at the highest matmul
    precision (module docstring for the switches). ``return_chosen``
    adds the routed experts of every sparse layer, (layers, B * T, k)."""
    eps = config["rms_norm_eps"]
    D = config["hidden_size"]
    B, T = tokens.shape
    rd = _rounder(round_to)
    block = min(block, T)
    choices = []
    with jax.default_matmul_precision("highest"):
        x = _f32(params[f"{name}_tok_embed_weight"])[tokens]
        for i in range(config["num_hidden_layers"]):
            p = f"{name}_l{i}"
            a = _rms_norm(x, params[f"{p}_ln1_gamma"], eps)
            c_q = _rms_norm(rd(a) @ rd(_f32(params[f"{p}_q_a_weight"])).T,
                            params[f"{p}_q_a_norm_gamma"], eps)
            att = attention(a, c_q, p, params, config, rd, block, yarn)
            x = x + rd(att) @ rd(_f32(params[f"{p}_proj_weight"])).T
            m = _rms_norm(x, params[f"{p}_ln2_gamma"], eps)
            if i < config["first_k_dense_replace"]:
                F = config["intermediate_size"]
                w = _f32(params[f"{p}_ffn_gate_up_weight"])
                x = x + _gated(m, w[:F].T, w[F:].T,
                               _f32(params[f"{p}_ffn_down_weight"]).T, rd)
            else:
                routed, shared, chosen = expert_layer(
                    m.reshape(B * T, D), p, params, config, rd)
                choices.append(chosen)
                x = x + (routed + shared).reshape(B, T, D)
        if tail is not None:
            x = x[:, T - tail:]
        x = _rms_norm(x, params[f"{name}_ln_f_gamma"], eps)
        logits = rd(x) @ rd(_f32(params[f"{name}_head_weight"])).T
    if return_chosen:
        return logits, jnp.stack(choices)
    return logits


def choice_flip_share(ours, theirs):
    """Share of (sparse layer, token) routing decisions in which the
    two sides chose different sets of experts."""
    a = jnp.sort(jnp.asarray(ours), axis=-1)
    b = jnp.sort(jnp.asarray(theirs), axis=-1)
    return jnp.mean(jnp.any(a != b, axis=-1).astype(jnp.float32))
