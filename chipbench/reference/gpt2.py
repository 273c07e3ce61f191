"""Plain reference: a GPT-2-style decoder in float32 jax.numpy.

The published description: Radford et al. 2019 (GPT-2) as configured by
cerebras/Cerebras-GPT-1.3B's config.json - learned absolute positions,
pre-LayerNorm blocks, multi-head causal attention scaled by
1/sqrt(head), a GeLU feed-forward of n_inner, a final LayerNorm and an
output head tied to the token embedding. No kernels, no cache, no
batching tricks: one full-sequence forward.

Departures from the published description (each one follows
mxnet_tpu/models/transformer.py, the program under test):
  * the token embedding is multiplied by sqrt(n_embd) before the
    position embedding is added (GPT-2 does not scale it);
  * GeLU is the exact erf form (Cerebras-GPT states "gelu"; GPT-2's
    original code uses the tanh approximation, "gelu_new");
  * the feed-forward's first bias lives on the FusedBiasGeLU node
    (``*_ffn_gelu_bias``), not on the matmul - the same mathematics.

Parameters are taken by the program's names and upcast leaf by leaf,
so no second copy of the model is held.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(gamma) + _f32(beta)


def _dense(x, w, b=None):
    """FullyConnected: weight is (out, in)."""
    y = x @ _f32(w).T
    return y if b is None else y + _f32(b)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0)))


def forward(params, tokens, config, name="lm"):
    """Logits (B, T, vocab) of ``tokens`` (B, T) int32 under
    ``params`` ({program name: array}), in float32 at the highest
    matmul precision."""
    d, heads = config["n_embd"], config["n_head"]
    eps = config.get("layer_norm_epsilon", 1e-5)
    dh = d // heads
    B, T = tokens.shape
    with jax.default_matmul_precision("highest"):
        emb = _f32(params[f"{name}_tok_embed_weight"])
        x = emb[tokens] * jnp.sqrt(jnp.float32(d))
        x = x + _f32(params[f"{name}_pos_embed_weight"])[:T][None]
        causal = jnp.tril(jnp.ones((T, T), bool))
        for i in range(config["n_layer"]):
            p = f"{name}_l{i}"
            h = _layer_norm(x, params[f"{p}_ln1_gamma"],
                            params[f"{p}_ln1_beta"], eps)
            qkv = _dense(h, params[f"{p}_qkv_weight"],
                         params[f"{p}_qkv_bias"])
            qkv = qkv.reshape(B, T, 3 * heads, dh).transpose(0, 2, 1, 3)
            q, k, v = (qkv[:, :heads], qkv[:, heads:2 * heads],
                       qkv[:, 2 * heads:])
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(dh))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
            a = a.transpose(0, 2, 1, 3).reshape(B, T, d)
            x = x + _dense(a, params[f"{p}_proj_weight"],
                           params[f"{p}_proj_bias"])
            h = _layer_norm(x, params[f"{p}_ln2_gamma"],
                            params[f"{p}_ln2_beta"], eps)
            h = _dense(h, params[f"{p}_ffn1_weight"])
            h = _gelu(h + _f32(params[f"{p}_ffn_gelu_bias"]))
            x = x + _dense(h, params[f"{p}_ffn2_weight"],
                           params[f"{p}_ffn2_bias"])
        x = _layer_norm(x, params[f"{name}_ln_f_gamma"],
                        params[f"{name}_ln_f_beta"], eps)
        return x @ emb.T
