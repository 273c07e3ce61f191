"""Plain reference: the Granite 4.0-H decoder (``model_type
granitemoehybrid``) in float32 jax.numpy - one full-sequence forward,
the recurrence step by step, no chunks, no cache, no kernels.

The published description: ibm-granite/granite-4.0-h-micro's
config.json (catalog row ``granite-4.0-h-micro`` of model-configs/
architectures.jsonl), the model code of ``transformers``'
``modeling_granitemoehybrid.py`` and the Mamba-2 paper
(arXiv:2405.21060). ``x0 = embedding_multiplier E[token]``; for each
layer ``l`` with ``m = residual_multiplier``:

    h = x + m Mixer_l(RMSNorm(x))        Mixer_l by layer_types[l]
    x = h + m W_out(silu(W_g n) * (W_u n)),   n = RMSNorm(h)

``logits = (RMSNorm(x) E^T) / logits_scaling`` (tied head).

**Attention** (``"attention"``): q of ``num_attention_heads`` heads, k
and v of ``num_key_value_heads`` heads of ``hidden_size /
num_attention_heads``, no bias, no rotary, no positions of any kind;
scores ``q k^T attention_multiplier`` (in place of ``1 / sqrt(head)``),
causal softmax, each K/V head read by ``heads / kv_heads`` consecutive
query heads, then ``W_o``.

**Mamba-2** (``"mamba"``), ``d_in = mamba_n_heads x mamba_d_head``, ``N
= mamba_d_state``, per token ``t`` and head ``h``:

    [z_t | xBC_t | dt_t] = W_in u_t               d_in | d_in + 2N | heads
    c_t   = silu(sum_{k<K} w_conv[:, k] xBC_{t-(K-1)+k} + b_conv)
    [x_t | B_t | C_t] = c_t
    dlt_t = softplus(dt_t + dt_bias)       a_t = exp(dlt_t A), A = -exp(A_log)
    H_t[h] = a_t[h] H_{t-1}[h] + dlt_t[h] x_t[h] (outer) B_t,   H_{-1} = 0
    y_t[h] = H_t[h] C_t + D[h] x_t[h]
    o_t   = W_out(w_norm * rmsnorm(y_t * silu(z_t)))

Readings the config leaves open (each also under the configuration's
``assumed``), as the published model code has them:
  * the projection's split order is ``z | xBC | dt`` and the
    convolution's channels ``x | B | C``;
  * the convolution is depthwise and causal over ``mamba_d_conv``
    inputs, zeros before the start, SiLU after it;
  * ``dt`` has no limits beyond softplus (``time_step_limit`` (0, inf));
  * the gated norm is over all ``d_in`` numbers (``mamba_n_groups`` 1),
    the gate ``silu(z)`` applied BEFORE the statistic, epsilon
    ``rms_norm_eps``;
  * ``D`` is one scalar a head;
  * ``embedding_multiplier`` scales the embedding's rows (not the tied
    head), ``residual_multiplier`` every sub-layer's output before it
    is added, ``attention_multiplier`` the scores, ``logits_scaling``
    divides the logits.

Departures in parameter LAYOUT only, following models/transformer.py
and ops/ssm.py (the program under test): q, k and v are the row blocks
of one ``*_qkv_weight``; gate and up the halves of one
``*_ffn_gate_up_weight``; the mixer's ``W_in`` is ``*_mamba_in_weight``,
its convolution ``*_mamba_conv_weight`` (channels, taps) and
``*_mamba_conv_bias``, ``*_mamba_dt_bias``, ``*_mamba_A_log``, ``*_mamba_D``,
the gated norm's gain ``*_mamba_norm_gamma``; both mixers' output
projection is ``*_proj_weight``.

Controls: ``round_to=`` rounds every matmul operand to that dtype first
(the nearest precision below the stated bfloat16 is float8_e4m3fn);
``state_dtype=`` rounds the state ``H`` to that dtype after every
token - the precision below the float32 the configuration states for
it; ``state_every=n`` drops the state that a token takes over,
``H_{t-1}``, at every token whose index is a multiple of n - 1: a state
that carries nothing from one token to the next; 256: a state that the
hand-over between two windows of 256 loses. ``tail=n`` returns
the logits of the last n positions alone, the head a block of the
vocabulary at a time. Parameters are taken by the program's names and
upcast where they are used, a layer at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, gamma, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(gamma)


def _rounder(round_to):
    """Round to ``round_to`` and stay float32. To bfloat16 by
    ``lax.reduce_precision``: a cast there and back inside an
    elementwise fusion is dropped by the TPU compiler (the state's
    rounding in ``mamba``'s scan read exactly 0.0 on the chip), and in
    front of a matmul both forms read alike."""
    if round_to is None:
        return lambda x: x
    if jnp.dtype(round_to) == jnp.bfloat16:
        return lambda x: jax.lax.reduce_precision(x, 8, 7)
    return lambda x: x.astype(round_to).astype(jnp.float32)


def _dense(x, w, rd):
    """FullyConnected without bias: weight is (out, in)."""
    return rd(x) @ rd(_f32(w)).T


def attention(n, p, params, cfg, rd):
    """Grouped attention without positions (module docstring): ``n`` (B,
    T, D) -> (B, T, D) before ``W_o``."""
    B, T, D = n.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = D // heads
    qkv = _dense(n, params[f"{p}_qkv_weight"], rd)
    q = qkv[..., :heads * dh].reshape(B, T, kv, heads // kv, dh)
    k = qkv[..., heads * dh:(heads + kv) * dh].reshape(B, T, kv, dh)
    v = qkv[..., (heads + kv) * dh:].reshape(B, T, kv, dh)
    s = jnp.einsum("bqcgd,bkcd->bcgqk", rd(q), rd(k)) \
        * jnp.float32(cfg["attention_multiplier"])
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None, None], s, -jnp.inf)
    a = jnp.einsum("bcgqk,bkcd->bqcgd", rd(jax.nn.softmax(s, axis=-1)),
                   rd(v))
    return a.reshape(B, T, D)


def mamba(n, p, params, cfg, rd, state_dtype=None, state_every=None):
    """The Mamba-2 mixer (module docstring), the recurrence one token
    at a time: ``n`` (B, T, D) -> (B, T, d_in) before ``W_out``."""
    B, T, _ = n.shape
    H, P, N, K = (cfg[k] for k in ("mamba_n_heads", "mamba_d_head",
                                   "mamba_d_state", "mamba_d_conv"))
    d_in = H * P
    C = d_in + 2 * N
    wide = _dense(n, params[f"{p}_mamba_in_weight"], rd)
    z, xbc, dt = wide[..., :d_in], wide[..., d_in:d_in + C], \
        wide[..., d_in + C:]
    w = _f32(params[f"{p}_mamba_conv_weight"])                 # (C, K)
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + T] * w[None, None, :, k] for k in range(K))
    c = jax.nn.silu(conv + _f32(params[f"{p}_mamba_conv_bias"]))
    x = c[..., :d_in].reshape(B, T, H, P)
    Bm, Cm = c[..., d_in:d_in + N], c[..., d_in + N:]
    dlt = jax.nn.softplus(dt + _f32(params[f"{p}_mamba_dt_bias"]))  # (B, T, H)
    A = -jnp.exp(_f32(params[f"{p}_mamba_A_log"]))
    keep = _rounder(state_dtype)
    kept = jnp.ones((T,), jnp.float32) if state_every is None \
        else _f32(jnp.arange(T) % state_every != 0)

    def step(h, row):
        x_t, d_t, b_t, c_t, m_t = row       # (B,H,P) (B,H) (B,N) (B,N) ()
        h = jnp.exp(d_t * A)[:, :, None, None] * (m_t * h) \
            + (d_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :]
        h = keep(h)
        return h, jnp.sum(h * c_t[:, None, None, :], axis=-1)

    swap = lambda a: jnp.swapaxes(a, 0, 1)                   # noqa: E731
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), jnp.float32),
                        (swap(x), swap(dlt), swap(Bm), swap(Cm), kept))
    y = swap(y) + _f32(params[f"{p}_mamba_D"])[None, None, :, None] * x
    gated = y.reshape(B, T, d_in) * jax.nn.silu(z)
    return _rms_norm(gated, params[f"{p}_mamba_norm_gamma"],
                     cfg["rms_norm_eps"])


def forward(params, tokens, config, name="lm", round_to=None,
            state_dtype=None, state_every=None, tail=None, head_blocks=8):
    """Logits (B, T, vocab) - or, with ``tail=n``, (B, n, vocab) of the
    last n positions - of ``tokens`` (B, T) int32 under ``params``
    ({program name: array}), in float32 at the highest matmul
    precision."""
    cfg, eps = config, config["rms_norm_eps"]
    m = jnp.float32(cfg["residual_multiplier"])
    rd = _rounder(round_to)
    with jax.default_matmul_precision("highest"):
        emb = params[f"{name}_tok_embed_weight"]
        x = _f32(jnp.asarray(emb)[tokens]) \
            * jnp.float32(cfg["embedding_multiplier"])
        for i, kind in enumerate(cfg["layer_types"]):
            p = f"{name}_l{i}"
            n = _rms_norm(x, params[f"{p}_ln1_gamma"], eps)
            mixed = mamba(n, p, params, cfg, rd, state_dtype, state_every) \
                if kind == "mamba" else attention(n, p, params, cfg, rd)
            x = x + m * _dense(mixed, params[f"{p}_proj_weight"], rd)
            n = _rms_norm(x, params[f"{p}_ln2_gamma"], eps)
            gu = _dense(n, params[f"{p}_ffn_gate_up_weight"], rd)
            F = gu.shape[-1] // 2
            x = x + m * _dense(jax.nn.silu(gu[..., :F]) * gu[..., F:],
                               params[f"{p}_ffn_down_weight"], rd)
        if tail is not None:
            x = x[:, x.shape[1] - tail:]
        x = rd(_rms_norm(x, params[f"{name}_ln_f_gamma"], eps))
        V = emb.shape[0]
        blocks = head_blocks if V % head_blocks == 0 else 1
        parts = jax.lax.map(
            lambda block: x @ rd(_f32(block)).T,
            jnp.asarray(emb).reshape(blocks, V // blocks, -1))
        logits = jnp.moveaxis(parts, 0, 2).reshape(x.shape[:2] + (V,))
        return logits / jnp.float32(cfg["logits_scaling"])
