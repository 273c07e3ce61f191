"""Plain reference: a rotary-position decoder in float32 jax.numpy.

The published description: Su et al. 2021 (RoFormer, arXiv:2104.09864)
positions in a GPT-style decoder - pre-LayerNorm blocks, multi-head
causal attention scaled by 1/sqrt(head) whose queries and keys are
rotated by their absolute position, a GeLU feed-forward of four times
the width, a final LayerNorm, a head tied to the token embedding, and
no position table. One full-sequence forward: no kernels, no cache.

Departures (each follows mxnet_tpu/models/transformer.py with
``pos_embed="rotary"``, the program under test):
  * the rotation pairs element i of a head with element i + head/2
    (the split-half form of GPT-NeoX), at angle pos * base**(-2i/head);
  * the token embedding is multiplied by sqrt(width);
  * GeLU is the exact erf form, its bias on the ``*_ffn_gelu_bias``
    node.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _norm(x, p, name, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * _f32(p[name + "_gamma"])
            + _f32(p[name + "_beta"]))


def _dense(x, p, name, bias=True):
    y = x @ _f32(p[name + "_weight"]).T          # weight is (out, in)
    return y + _f32(p[name + "_bias"]) if bias else y


def _rotate(x, base):
    """x: (B, H, T, dh), position t on axis 2."""
    dh = x.shape[-1]
    half = dh // 2
    freq = jnp.float32(base) ** (-jnp.arange(half, dtype=jnp.float32)
                                 * (2.0 / dh))
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def forward(params, tokens, config, name="lm"):
    """Logits (B, T, vocab) float32 of ``tokens`` (B, T) int32."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    eps, base = config["layer_norm_eps"], config["rope_theta"]
    dh = d // heads
    B, T = tokens.shape
    with jax.default_matmul_precision("highest"):
        emb = _f32(params[f"{name}_tok_embed_weight"])
        x = emb[tokens] * jnp.sqrt(jnp.float32(d))
        causal = jnp.tril(jnp.ones((T, T), bool))
        for i in range(config["num_hidden_layers"]):
            p = f"{name}_l{i}"
            qkv = _dense(_norm(x, params, p + "_ln1", eps), params,
                         p + "_qkv")
            qkv = qkv.reshape(B, T, 3, heads, dh).transpose(2, 0, 3, 1, 4)
            q, k, v = _rotate(qkv[0], base), _rotate(qkv[1], base), qkv[2]
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(dh))
            s = jnp.where(causal, s, -jnp.inf)
            a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
            a = a.transpose(0, 2, 1, 3).reshape(B, T, d)
            x = x + _dense(a, params, p + "_proj")
            h = _dense(_norm(x, params, p + "_ln2", eps), params,
                       p + "_ffn1", bias=False)
            h = h + _f32(params[p + "_ffn_gelu_bias"])
            h = 0.5 * h * (1.0 + jax.lax.erf(h / jnp.sqrt(2.0)))
            x = x + _dense(h, params, p + "_ffn2")
        return _norm(x, params, f"{name}_ln_f", eps) @ emb.T
