"""Plain reference: the EvaByte decoder in float32 jax.numpy.

The published description: EvaByte 6.5B (huggingface.co/EvaByte/EvaByte,
``config.json`` and the model code beside it), whose attention is EVA
("Efficient Attention via Control Variates", Zheng et al., ICLR 2023)
in the deterministic chunked form of the model's ``eva.py``. Per layer,
pre-norm, no bias anywhere, the residual stream in float32:

    N(x)   = x / sqrt(mean(x^2) + eps) * (1 + g)     norm_add_unit_offset
    h      = x + Wo . EVA(Wq N(x), Wk N(x), Wv N(x))
    y      = h + Wdown(silu(Wgate N(h)) * (Wup N(h)))
    logits = Whead N(y_last): num_pred_heads x vocab columns, head 0 is
             the next byte; Whead untied; the embedding is not scaled

EVA attention, per head (width d, s = d**-0.5, chunk C, window W), with
q_t, k_t rotated at the absolute position t (rotate-half pairs
(i, i + d/2), theta ``rope_theta``) and two learned vectors per head
and layer, phi and mu (the published ``adaptive_phi``,
``adaptive_mu_k``):

    chunk c = positions C*c .. C*c + C-1
    a_j  = softmax over j in c of (s * phi . k_j)
    k~_c = sum_j a_j k_j + mu            v~_c = sum_j a_j v_j
    query t, window w = t // W: ONE softmax over
        the exact rows {j : j // W == w, j <= t}  and
        the summaries  {c : (C*c) // W < w}
    o_t  = (sum_j e^{s q_t.k_j} v_j + sum_c e^{s q_t.k~_c} v~_c)
           / (sum_j e^{s q_t.k_j} + sum_c e^{s q_t.k~_c})

so a closed window is seen only through its W/C summaries, the first
token of a window sees itself and the summaries, and the windows are
blocks, not a sliding window.

**Four readings of the published eva.py that the catalog's config does
not carry** (the configuration lists them under ``assumed``; each is a
reading, not a fact):
  1. the rotary embedding is applied to k before the pooling, and not
     again to k~;
  2. the factor s stands inside the pooling softmax;
  3. mu is added to k~ only (v~ has no offset);
  4. the head's layout is ``num_pred_heads`` consecutive blocks of
     ``vocab_size`` columns, block 0 the next byte.

No kernels, no cache, no windows of state: one full-sequence forward
whose masks are built from positions. Attention is computed one head at
a time (``lax.map``) and every weight is upcast where it is used, so
that beside a live engine no second float32 copy of the model, and no
(B, H, T, T) scores, are ever held.

Departures from the published code - parameter LAYOUT only, following
mxnet_tpu/models/transformer.py (the program under test):
  * ``q_proj``, ``k_proj``, ``v_proj`` are the three row blocks of one
    ``*_qkv_weight`` (3 * hidden, hidden);
  * ``gate_proj`` and ``up_proj`` are the two row blocks of one
    ``*_ffn_gate_up_weight`` (2 * intermediate, hidden).

``round_to=`` rounds every matmul operand (weights and activations) to
that dtype first: a compute path of lower precision than the one
stated. ``state_to=`` rounds only what a cache would hold - the rotated
k, v and the summaries - to that dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rounder(to):
    if to is None:
        return lambda x: x
    return lambda x: x.astype(to).astype(jnp.float32)


def rms_norm(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + _f32(g))


def rope(x, theta):
    """x (B, H, T, d): rotate the pair (i, i + d/2) of position t by
    t * theta**(-2i/d) (rotate-half)."""
    d = x.shape[-1]
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def summaries(k, v, phi, mu, chunk):
    """(B, H, T, d) rotated k and v -> ``(k~, v~)`` of every chunk,
    (B, H, ceil(T / chunk), d). A last chunk that T cuts short is
    padded with zero rows; no query ever sees it."""
    B, H, T, d = k.shape
    n = -(-T // chunk)
    grow = ((0, 0), (0, 0), (0, n * chunk - T), (0, 0))
    kc = jnp.pad(k, grow).reshape(B, H, n, chunk, d)
    vc = jnp.pad(v, grow).reshape(B, H, n, chunk, d)
    logit = jnp.einsum("bhncd,hd->bhnc", kc, _f32(phi)) * d ** -0.5
    a = jax.nn.softmax(logit, axis=-1)
    ks = jnp.einsum("bhnc,bhncd->bhnd", a, kc) \
        + _f32(mu)[None, :, None, :]
    return ks, jnp.einsum("bhnc,bhncd->bhnd", a, vc)


def eva_attention(q, k, v, phi, mu, window, chunk, round_to=None,
                  state_to=None):
    """EVA attention of rotated q, k and v (B, H, T, d): (B, H, T, d)
    float32."""
    B, H, T, d = q.shape
    rd, st = _rounder(round_to), _rounder(state_to)
    k, v = st(k), st(v)
    ks, vs = summaries(k, v, phi, mu, chunk)
    ks, vs = st(ks), st(vs)
    t = jnp.arange(T)
    exact = (t[None, :] // window == t[:, None] // window) \
        & (t[None, :] <= t[:, None])                        # (T, T)
    c = jnp.arange(ks.shape[2])
    pooled = (c[None, :] * chunk) // window < t[:, None] // window
    mask = jnp.concatenate([exact, pooled], axis=1)[None]

    def one_head(xs):
        qh, kh, vh, ksh, vsh = xs                           # (B, ., d)
        keys = rd(jnp.concatenate([kh, ksh], axis=1))
        s = jnp.einsum("bqd,bkd->bqk", rd(qh), keys) * d ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", rd(p),
                          rd(jnp.concatenate([vh, vsh], axis=1)))

    heads = [x.transpose(1, 0, 2, 3) for x in (q, k, v, ks, vs)]
    return jax.lax.map(one_head, tuple(heads)).transpose(1, 0, 2, 3)


def forward(params, tokens, config, name="lm", all_heads=False,
            round_to=None, state_to=None):
    """Logits of ``tokens`` (B, T) int32 under ``params`` ({program
    name: array}), float32 at the highest matmul precision: head 0's
    (B, T, vocab), or with ``all_heads`` (B, T, num_pred_heads,
    vocab)."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    window, chunk = config["window_size"], config["chunk_size"]
    n_pred, vocab = config["num_pred_heads"], config["vocab_size"]
    inter = config["intermediate_size"]
    dh = d // heads
    B, T = tokens.shape
    rd = _rounder(round_to)

    def mm(x, w):
        return rd(x) @ rd(_f32(w)).T

    with jax.default_matmul_precision("highest"):
        x = _f32(params[f"{name}_tok_embed_weight"])[tokens]
        for i in range(config["num_hidden_layers"]):
            p = f"{name}_l{i}"
            qkv = mm(rms_norm(x, params[f"{p}_ln1_gamma"], eps),
                     params[f"{p}_qkv_weight"])
            q, k, v = (t.reshape(B, T, heads, dh).transpose(0, 2, 1, 3)
                       for t in (qkv[..., :d], qkv[..., d:2 * d],
                                 qkv[..., 2 * d:]))
            att = eva_attention(rope(q, theta), rope(k, theta), v,
                                params[f"{p}_attn_phi"],
                                params[f"{p}_attn_mu"], window, chunk,
                                round_to=round_to, state_to=state_to)
            att = att.transpose(0, 2, 1, 3).reshape(B, T, d)
            x = x + mm(att, params[f"{p}_proj_weight"])
            gu = mm(rms_norm(x, params[f"{p}_ln2_gamma"], eps),
                    params[f"{p}_ffn_gate_up_weight"])
            x = x + mm(jax.nn.silu(gu[..., :inter]) * gu[..., inter:],
                       params[f"{p}_ffn_down_weight"])
        x = rms_norm(x, params[f"{name}_ln_f_gamma"], eps)
        logits = mm(x, params[f"{name}_head_weight"]) \
            .reshape(B, T, n_pred, vocab)
    return logits if all_heads else logits[:, :, 0]
