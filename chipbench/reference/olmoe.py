"""Plain reference: the OLMoE decoder in float32 jax.numpy.

The published description: Muennighoff et al. 2024 (arXiv:2409.02060)
as configured by allenai/OLMoE-1B-7B-0125-Instruct's config.json and
computed by the published model code (``modeling_olmoe.py``):

    a      = RMSNorm(x)
    h      = x + Wo . Attn(RoPE(RMSNorm_q(Wq a)), RoPE(RMSNorm_k(Wk a)), Wv a)
             RMSNorm_q, RMSNorm_k over the whole projection (all heads
             together), before the split into heads; RoPE rotate-half
             pairs (i, i + head/2), theta ``rope_theta``; scores /
             sqrt(head); causal; no bias anywhere
    m      = RMSNorm(h)
    r      = softmax_float32(Wg m)                over all experts
    S      = top-k of r; weights r[e], e in S, renormalised only under
             ``norm_topk_prob``
    y      = h + sum_{e in S} r[e] Wdown_e(silu(Wgate_e m) * (Wup_e m))
    logits = Whead RMSNorm(y), Whead untied; the embedding is not scaled

No kernels, no cache, no sorting or grouping: one full-sequence forward
that computes EVERY expert's output for every token and masks it by the
top-k weights. The experts are visited one at a time and upcast inside
the loop (``lax.scan`` over the stacked weights), so that beside a live
engine no second float32 copy of the model is ever held.

Departures from the published code - parameter LAYOUT only, each one
following mxnet_tpu/models/transformer.py and ops/moe.py (the program
under test), none changing the mathematics:
  * ``q_proj``, ``k_proj``, ``v_proj`` are the three row blocks of one
    ``*_qkv_weight`` (3 * hidden, hidden);
  * the experts are stacked on a leading axis and stored transposed,
    K-major: ``*_moe_gate_weight``/``*_moe_up_weight`` (E, hidden,
    inter) are ``gate_proj.weight.T``/``up_proj.weight.T`` and
    ``*_moe_down_weight`` (E, inter, hidden) is ``down_proj.weight.T``;
  * the router's ``mlp.gate.weight`` is ``*_moe_router_weight``.
The published code casts the top-k weights to the hidden dtype before
the weighted sum; in float32 that is the identity.

``routing=`` takes the experts chosen for every (layer, sequence,
position) from outside - an int array (layers, B, T, k) - and weights
them by this forward's own float32 probabilities: routing is
discontinuous (a token whose k-th and (k+1)-th probabilities lie within
rounding goes elsewhere), so a comparison can be made free, or with the
routing forced to the other side's choice. ``round_to=`` rounds every
matmul operand (weights and activations) to that dtype first: the
control, a compute path of lower precision than the one stated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, gamma, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(gamma)


def _rope(x, theta):
    """x (B, H, T, dh): rotate the pair (i, i + dh/2) of position t by
    t * theta**(-2i/dh) (rotate-half)."""
    dh = x.shape[-1]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _rounder(round_to):
    if round_to is None:
        return lambda x: x
    return lambda x: x.astype(round_to).astype(jnp.float32)


def expert_layer(m, router_w, gate_w, up_w, down_w, top_k, norm_topk,
                 routing=None, round_to=None):
    """The expert feed-forward of rows ``m`` (N, hidden) float32:
    ``(output (N, hidden), experts chosen (N, k) int32)``. Every expert
    is computed for every row, one expert at a time, and masked by the
    top-k weights."""
    rd = _rounder(round_to)
    probs = jax.nn.softmax(rd(m) @ rd(_f32(router_w)).T, axis=-1)
    _, chosen = jax.lax.top_k(probs, top_k)
    if routing is not None:
        chosen = routing
    n_expert = gate_w.shape[0]
    # (N, E): the probability of each chosen expert, 0 elsewhere
    picked = jnp.any(chosen[:, :, None]
                     == jnp.arange(n_expert)[None, None, :], axis=1)
    weight = jnp.where(picked, probs, 0.0)
    if norm_topk:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def one_expert(acc, xs):
        gate, up, down, w = xs          # this expert's matrices, upcast
        h = jax.nn.silu(rd(m) @ rd(_f32(gate))) * (rd(m) @ rd(_f32(up)))
        return acc + w[:, None] * (rd(h) @ rd(_f32(down))), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                          (gate_w, up_w, down_w, weight.T))
    return out, chosen.astype(jnp.int32)


def forward(params, tokens, config, name="lm", routing=None,
            round_to=None, return_routing=False):
    """Logits (B, T, vocab) of ``tokens`` (B, T) int32 under ``params``
    ({program name: array}), in float32 at the highest matmul
    precision; with ``return_routing`` also the experts chosen, int32
    (layers, B, T, k)."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    if config.get("num_key_value_heads", heads) != heads:
        raise ValueError("reference/olmoe.py: grouped-query attention is "
                         "not part of this configuration")
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    top_k = config["num_experts_per_tok"]
    norm_topk = bool(config.get("norm_topk_prob", False))
    dh = d // heads
    B, T = tokens.shape
    rd = _rounder(round_to)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _f32(params[f"{name}_tok_embed_weight"])[tokens]
        causal = jnp.tril(jnp.ones((T, T), bool))
        for i in range(config["num_hidden_layers"]):
            p = f"{name}_l{i}"
            a = _rms_norm(x, params[f"{p}_ln1_gamma"], eps)
            qkv = rd(a) @ rd(_f32(params[f"{p}_qkv_weight"])).T
            q, k, v = qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:]
            q = _rms_norm(q, params[f"{p}_q_norm_gamma"], eps)
            k = _rms_norm(k, params[f"{p}_k_norm_gamma"], eps)
            q, k, v = (t.reshape(B, T, heads, dh).transpose(0, 2, 1, 3)
                       for t in (q, k, v))
            q, k = _rope(q, theta), _rope(k, theta)
            s = jnp.einsum("bhqd,bhkd->bhqk", rd(q), rd(k)) / jnp.sqrt(
                jnp.float32(dh))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            att = jnp.einsum("bhqk,bhkd->bhqd",
                             rd(jax.nn.softmax(s, axis=-1)), rd(v))
            att = att.transpose(0, 2, 1, 3).reshape(B, T, d)
            x = x + rd(att) @ rd(_f32(params[f"{p}_proj_weight"])).T
            m = _rms_norm(x, params[f"{p}_ln2_gamma"], eps)
            y, sel = expert_layer(
                m.reshape(B * T, d), params[f"{p}_moe_router_weight"],
                params[f"{p}_moe_gate_weight"],
                params[f"{p}_moe_up_weight"],
                params[f"{p}_moe_down_weight"], top_k, norm_topk,
                routing=None if routing is None
                else routing[i].reshape(B * T, top_k),
                round_to=round_to)
            chosen.append(sel.reshape(B, T, top_k))
            x = x + y.reshape(B, T, d)
        x = _rms_norm(x, params[f"{name}_ln_f_gamma"], eps)
        logits = rd(x) @ rd(_f32(params[f"{name}_head_weight"])).T
    if return_routing:
        return logits, jnp.stack(chosen)
    return logits


def routing_flip_share(ours, theirs):
    """Share of (layer, sequence, position) decisions in which the two
    sides chose different SETS of experts (the order inside a set does
    not matter)."""
    a = jnp.sort(jnp.asarray(ours), axis=-1)
    b = jnp.sort(jnp.asarray(theirs), axis=-1)
    return jnp.mean(jnp.any(a != b, axis=-1).astype(jnp.float32))
