"""Plain reference: the Xing4.0 decoder (``model_type xing4_0``) in
float32 jax.numpy - one full-sequence forward without cache, kernels or
batching.

The published description: XingChen-AGI/Xing4.0-29B-A4B's config.json.
Its attention and experts are the DeepSeek-V3 family's (arXiv:2412.19437)
as ``reference/axk1.py`` states them - multi-head latent attention over
EVERY earlier position, the rotary under YaRN (here factor 64 over 4,096
positions: softmax scale (128 + 64) ** -0.5 * (0.1 ln 64 + 1) ** 2 =
0.14468, cos and sin times 1), experts of gated SiLU beside one shared
expert; that file's ``attention``, ``yarn`` and norm are used as they
stand. What is Xing4.0's own:

**The residual stream is ``n = hc_mult`` copies a token** and every
sub-layer ``F`` (attention: RMSNorm, MLA, output projection; feed-
forward: RMSNorm, the dense gated SiLU or the routed experts plus the
shared one) reads and writes it through manifold-constrained hyper-
connections (mHC, arXiv:2512.24880, over hyper-connections,
arXiv:2409.19606). Per token ``X`` in R^{n x C}; per sub-layer ``W`` in
R^{(2n + n^2) x nC} (rows: ``n`` of W_pre, ``n`` of W_post, ``n^2`` of
W_res row-major), ``b`` likewise, ``a = (a_pre, a_post, a_res)``:

    r     = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)   no gain
    Hpre  = sigmoid(a_pre (W_pre r) + b_pre)                        (n,)
    Hpost = 2 sigmoid(a_post (W_post r) + b_post)                   (n,)
    M0    = exp(clip(a_res mat(W_res r) + b_res, clamp_min, clamp_max))
    hc_sinkhorn_iters times: M <- M / (rowsum(M) + hc_eps);
                             M <- M / (colsum(M) + hc_eps)
    Hres  = M                     doubly stochastic up to hc_eps   (n, n)
    u     = sum_i Hpre[i] X[i]                      what F reads   (C,)
    X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] F(u)

The embedding row is copied into all ``n`` rows; after the last layer the
``n`` rows are summed, and the final RMSNorm and the head follow.

**The router** is ``noaux_tc``: ``sc = sigmoid(Wr a)`` over all
``n_routed_experts``; the choice is made on ``sc + bias`` (in ``n_group``
groups of consecutive experts, a group's score the sum of its two
largest ``sc + bias``, the ``topk_group`` best groups kept - with the
published ``n_group`` 1 there is one group and nothing is limited),
the ``num_experts_per_tok`` largest chosen (ties: the lowest index);
``g_e = sc_e / (sum_chosen(sc) + 1e-20) * routed_scaling_factor`` from
the unbiased scores. Every expert is held.

Readings the catalog's config leaves open (each also under the
configuration's ``assumed``):
  * the mappings are the mHC paper's (sigmoid read, 2 x sigmoid write-
    back, Sinkhorn-projected mix); the config gives only ``hc_mult``,
    ``hc_sinkhorn_iters``, ``hc_eps`` and the clamp's two ends;
  * the clamp acts on the mix's logits before ``exp``;
  * ``hc_eps`` sits in the Sinkhorn denominators, ``rms_norm_eps`` in
    the one statistic over all ``nC`` numbers, which has no gain;
  * the stream starts as ``n`` copies of the embedding and ends as the
    sum of its rows (the hyper-connections paper's choice);
  * the rotary turns adjacent pairs (``reference/axk1.py``);
  * the multi-token-prediction layer (``num_nextn_predict_layers`` 1) is
    a drafter beside the model and is not part of this forward.

Departures in parameter LAYOUT only, following models/transformer.py,
ops/moe.py and ops/mhc.py (the program under test): ``reference/
axk1.py``'s, and a sub-layer's ``W``, ``b`` and ``a`` are
``*_{proj,ffn}_mhc_weight`` (2n + n^2, nC), ``_bias`` and ``_scale``.

Controls: ``round_to=`` rounds every matmul operand to that dtype first
(``reference/axk1.py``); ``mapping_dtype=`` rounds the mapping's own
arithmetic - the statistic's ``r``, the projection's operands and
result, the sigmoids, every Sinkhorn iterate, ``u`` and ``X'`` - to
that dtype: the precision below the float32 the configuration states
for it. ``tail=n`` returns the logits of the last n positions alone
(the head is taken a block of the vocabulary at a time: its float32
copy is 1.9 GB); sequences go through one at a time, an expert at a
time, so that 4,112 positions fit beside a live engine.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.axk1 import (_f32, _gated, _rms_norm, _rounder,
                                      attention, choice_flip_share)

__all__ = ["forward", "mhc_mapping", "route", "choice_flip_share"]

#: rows of the head taken at a time
_HEAD_BLOCK = 8192


def mhc_mapping(X, w, b, a, cfg, rd=None):
    """``(Hpre (N, n), Hpost (N, n), Hres (N, n, n))`` of the streams
    ``X`` (N, n, C) under one sub-layer's ``w``, ``b``, ``a`` (module
    docstring); ``rd`` rounds the mapping's arithmetic."""
    rd = rd or (lambda v: v)
    N, n, C = X.shape
    flat = X.reshape(N, n * C)
    r = rd(flat * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
        + cfg["rms_norm_eps"]))
    a = _f32(a)
    proj = rd(r @ rd(_f32(w)).T) * jnp.repeat(
        a, jnp.asarray([n, n, n * n]), total_repeat_length=2 * n + n * n) \
        + _f32(b)
    hpre = rd(jax.nn.sigmoid(proj[:, :n]))
    hpost = rd(2.0 * jax.nn.sigmoid(proj[:, n:2 * n]))
    m = rd(jnp.exp(jnp.clip(proj[:, 2 * n:].reshape(N, n, n),
                            cfg["mhc_h_res_clamp_min"],
                            cfg["mhc_h_res_clamp_max"])))
    eps = cfg["hc_eps"]
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = rd(m / (jnp.sum(m, axis=2, keepdims=True) + eps))
        m = rd(m / (jnp.sum(m, axis=1, keepdims=True) + eps))
    return hpre, hpost, m


def route(sc, bias, cfg):
    """The ``noaux_tc`` choice over scores ``sc`` (N, E): ``(chosen
    (N, k) int32, largest ``sc + bias`` first; weight (N, E) float32, 0
    off the chosen)``. Sorts, stable, so that a tie goes to the lowest
    index."""
    N, E = sc.shape
    k, G = cfg["num_experts_per_tok"], cfg["n_group"]
    choice = sc + _f32(bias)
    kept = jnp.ones((N, E), bool)
    if G > 1:
        groups = choice.reshape(N, G, E // G)
        score = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)
        place = jnp.argsort(jnp.argsort(-score, axis=-1, stable=True),
                            axis=-1)
        kept = jnp.repeat(place < cfg["topk_group"], E // G, axis=-1)
    order = jnp.argsort(jnp.where(kept, -choice, jnp.inf), axis=-1,
                        stable=True)
    chosen = order[:, :k]
    picked = jnp.any(chosen[:, :, None] == jnp.arange(E)[None, None, :],
                     axis=1)
    weight = jnp.where(picked, sc, 0.0)
    if cfg["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weight * cfg["routed_scaling_factor"]


def expert_layer(m, p, params, cfg, rd):
    """The sparse feed-forward of rows ``m`` (N, D): every row's
    weighted sum over all the experts, one expert at a time, and the
    shared expert: ``(routed + shared (N, D), chosen (N, k))``."""
    sc = jax.nn.sigmoid(rd(m) @ rd(_f32(params[f"{p}_moe_router_weight"])).T)
    chosen, weight = route(sc, params[f"{p}_moe_router_bias"], cfg)

    def one_expert(acc, xs):
        gate, up, down, w = xs
        return acc + w[:, None] * _gated(m, gate, up, down, rd), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (params[f"{p}_moe_gate_weight"], params[f"{p}_moe_up_weight"],
         params[f"{p}_moe_down_weight"], weight.T))
    shared = _gated(m, params[f"{p}_moe_shared_gate_weight"],
                    params[f"{p}_moe_shared_up_weight"],
                    params[f"{p}_moe_shared_down_weight"], rd)
    return routed + shared, chosen


def _hyper(X, p, sub, params, cfg, md, F):
    """One sub-layer ``F`` through its hyper-connection: ``X`` (T, n, C)
    -> ``X'``."""
    hpre, hpost, hres = mhc_mapping(
        X, params[f"{p}_{sub}_mhc_weight"], params[f"{p}_{sub}_mhc_bias"],
        params[f"{p}_{sub}_mhc_scale"], cfg, md)
    u = md(jnp.einsum("ti,tic->tc", hpre, X))
    y = F(u)
    return md(jnp.einsum("tij,tjc->tic", hres, X)
              + hpost[:, :, None] * y[:, None, :])


def _one_sequence(params, tokens, config, name, rd, md, tail, block, yarn):
    """``(logits (tail, V), chosen (sparse layers, T, k))`` of one
    sequence ``tokens`` (T,)."""
    eps = config["rms_norm_eps"]
    n = config["hc_mult"]
    T = tokens.shape[0]
    choices = []
    x = _f32(jnp.asarray(params[f"{name}_tok_embed_weight"])[tokens])  # (T, C)
    X = jnp.repeat(x[:, None, :], n, axis=1)                       # (T, n, C)
    for i in range(config["num_hidden_layers"]):
        p = f"{name}_l{i}"

        def attend(u, p=p):
            a = _rms_norm(u, params[f"{p}_ln1_gamma"], eps)[None]
            c_q = _rms_norm(rd(a) @ rd(_f32(params[f"{p}_q_a_weight"])).T,
                            params[f"{p}_q_a_norm_gamma"], eps)
            att = attention(a, c_q, p, params, config, rd, block, yarn)[0]
            return rd(att) @ rd(_f32(params[f"{p}_proj_weight"])).T

        def feed_forward(u, p=p, i=i):
            m = _rms_norm(u, params[f"{p}_ln2_gamma"], eps)
            if i < config["first_k_dense_replace"]:
                F = config["intermediate_size"]
                w = _f32(params[f"{p}_ffn_gate_up_weight"])
                return _gated(m, w[:F].T, w[F:].T,
                              _f32(params[f"{p}_ffn_down_weight"]).T, rd)
            out, chosen = expert_layer(m, p, params, config, rd)
            choices.append(chosen)
            return out

        X = _hyper(X, p, "proj", params, config, md, attend)
        X = _hyper(X, p, "ffn", params, config, md, feed_forward)
    x = jnp.sum(X, axis=1)[T - tail:]
    x = rd(_rms_norm(x, params[f"{name}_ln_f_gamma"], eps))
    head = jnp.asarray(params[f"{name}_head_weight"])
    V = head.shape[0]
    rows = _HEAD_BLOCK if V % _HEAD_BLOCK == 0 else V
    logits = jax.lax.map(lambda w: x @ rd(_f32(w)).T,
                         head.reshape(V // rows, rows, head.shape[1]))
    return jnp.moveaxis(logits, 0, 1).reshape(tail, V), jnp.stack(choices)


def forward(params, tokens, config, name="lm", round_to=None,
            mapping_dtype=None, tail=None, block=128, yarn="yarn",
            return_chosen=False):
    """Logits (B, T or ``tail``, vocab) of ``tokens`` (B, T) int32 under
    ``params`` ({program name: array}), float32 at the highest matmul
    precision (module docstring for the switches). ``return_chosen``
    adds the routed experts of every sparse layer, (layers, B * T, k)."""
    B, T = tokens.shape
    rd, md = _rounder(round_to), _rounder(mapping_dtype)
    with jax.default_matmul_precision("highest"):
        logits, chosen = jax.lax.map(
            lambda seq: _one_sequence(params, seq, config, name, rd, md,
                                      tail or T, min(block, T), yarn),
            tokens)
    if return_chosen:
        L, k = chosen.shape[1], chosen.shape[-1]
        return logits, jnp.moveaxis(chosen, 0, 1).reshape(L, B * T, k)
    return logits
