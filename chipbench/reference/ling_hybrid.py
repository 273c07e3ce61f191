"""Plain reference: the Ling-3.0 decoder (``model_type bailing_hybrid``)
in float32 jax.numpy - one full-sequence forward, the delta rule one
token at a time, no chunks, no cache, no kernels.

The published description: inclusionAI/Ling-3.0-flash's config.json
(catalog row ``Ling-3.0-flash`` of model-configs/architectures.jsonl),
the Kimi Linear report (arXiv:2510.26692) for the linear layers and the
DeepSeek-V3 family's papers (arXiv:2405.04434, arXiv:2412.19437) for
the latent attention and the experts, as ``reference/axk1.py`` and
``reference/xing4.py`` state them. ``x0 = E[token]``; every norm is
RMSNorm(eps ``rms_norm_eps``), no bias anywhere, untied head; for each
layer that is run (``layers_run``: its published index ``i``):

    x = x + W_o Mixer_i(RMSNorm(x))      Mixer_i is latent attention where
                                         (i + 1) % layer_group_size == 0,
                                         KDA elsewhere
    x = x + FF_i(RMSNorm(x))             dense gated SiLU below
                                         first_k_dense_replace, else experts

**KDA** (``num_attention_heads`` H heads of ``head_dim`` D, keys and
values alike), per token ``t``, all of it float32:

    [q~ | k~ | v~ | f | g | b]_t = W_in n_t        H D each, b: H numbers
    [q, k, v]_t = silu(sum_j w_conv[:, j] [q~, k~, v~]_{t-(K-1)+j})
                  K = short_conv_kernel_size, depthwise, causal, zeros
                  before the start, no bias
    q_t[h] = q_t[h] / sqrt(|q_t[h]|^2 + 1e-6) * D^-0.5
    k_t[h] = k_t[h] / sqrt(|k_t[h]|^2 + 1e-6)
    log a_t = kda_lower_bound * sigmoid(exp(A_log[h]) * (f_t + dt_bias))
    b_t[h] = sigmoid(b_t[h])
    S_t[h] = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1}[h] + b_t k_t v_t^T
    o_t[h] = S_t[h]^T q_t[h]                       S_{-1} = 0
    Mixer  = w_norm * rmsnorm_D(o_t[h]) * sigmoid(g_t)[h]

**Latent attention**: ``reference/axk1.py``'s (the latent row normed,
adjacent rotary pairs, every position at or before the query), with the
query one projection ``W_q n`` (``q_lora_rank`` null), the plain rotary
at ``rope_theta`` (``rope_scaling`` null), softmax scale ``(128 + 64)
** -0.5``, and each head's output times ``sigmoid(W_gate n)[h]``
before ``W_o``.

**Experts**: ``reference/xing4.py``'s ``noaux_tc`` choice - ``sc =
sigmoid(W_r a)``, the choice on ``sc + bias`` inside the ``topk_group``
best of ``n_group`` groups, weights from ``sc`` itself, normed, times
``routed_scaling_factor`` - as a loop over the experts HELD here
(``num_experts_held`` from ``held_first``), beside the shared expert:
what an absent expert would add is left out, here as in the program.

Readings the config leaves open (each also under the configuration's
``assumed``): the layer rule above (the ``bailing`` linear family's;
``described_as`` says 3 : 1, the config ``layer_group_size`` 6);
``num_kv_heads_for_linear_attn`` 0 as "the query's count"; the bounded
decay (``kda_safe_gate``); the query's scale ``D^-0.5`` and the 1e-6
under the 2-norms' roots (the public kernel's); ``use_qk_norm`` as what
both mixers have by definition (no further per-head norm); the gate as
one number a head; no swiglu clamp (the layers run carry a limit of 0);
the multi-token-prediction layer is a drafter beside the model and not
part of this forward.

Departures in parameter LAYOUT only, following models/transformer.py,
ops/kda.py, ops/mla.py and ops/moe.py (the program under test): the six
KDA projections are the row blocks of one ``*_kda_in_weight``; the three
convolutions one ``*_kda_conv_weight`` (3 H D, K); ``*_kda_A_log``,
``*_kda_dt_bias``, ``*_kda_norm_weight``; the direct query
``*_q_weight``, the gate ``*_gate_weight``; both mixers' output
projection ``*_proj_weight``; the rest as ``reference/axk1.py`` has it.

Controls: ``round_to=`` rounds every matmul operand to that dtype first
(the nearest precision below the stated bfloat16 is float8_e4m3fn; to
bfloat16 by ``lax.reduce_precision``, which the compiler cannot drop);
``state_dtype=`` rounds the matrix state ``S`` to that dtype after
every token - the precision below the float32 the configuration states
for it; ``state_every=n`` drops the state a token takes over at every
token whose index is a multiple of n (1: a state that carries nothing;
``prefill_chunk``: the hand-over between two windows lost).
``tail=n`` returns the logits of the last n positions alone, the head
a block of the vocabulary at a time; attention's queries go ``block``
at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.axk1 import (_by_query_block, _f32, _gated,
                                      _rms_norm, _rope, choice_flip_share,
                                      yarn)
from chipbench.reference.xing4 import route

__all__ = ["forward", "layer_types", "kda", "choice_flip_share"]


def layer_types(cfg):
    """``"kda"`` or ``"mla"`` for each layer that is run, by its
    published index."""
    period = cfg["layer_group_size"]
    return ["mla" if (i + 1) % period == 0 else "kda"
            for i in cfg["layers_run"]]


def _rounder(round_to):
    """Round to ``round_to`` and stay float32 (``reference/
    granite_hybrid.py``: to bfloat16 by ``lax.reduce_precision``)."""
    if round_to is None:
        return lambda x: x
    if jnp.dtype(round_to) == jnp.bfloat16:
        return lambda x: jax.lax.reduce_precision(x, 8, 7)
    return lambda x: x.astype(round_to).astype(jnp.float32)


def _dense(x, w, rd):
    """FullyConnected without bias: weight is (out, in)."""
    return rd(x) @ rd(_f32(w)).T


def kda(n, p, params, cfg, rd, state_dtype=None, state_every=None):
    """The KDA mixer (module docstring), the delta rule one token at a
    time: ``n`` (B, T, hidden) -> (B, T, H D) before ``W_o``."""
    B, T, _ = n.shape
    H, D, K = (cfg[k] for k in ("num_attention_heads", "head_dim",
                                "short_conv_kernel_size"))
    HD = H * D
    wide = _dense(n, params[f"{p}_kda_in_weight"], rd)
    w = _f32(params[f"{p}_kda_conv_weight"])                   # (3 HD, K)
    padded = jnp.pad(wide[..., :3 * HD], ((0, 0), (K - 1, 0), (0, 0)))
    act = jax.nn.silu(sum(padded[:, j:j + T] * w[None, None, :, j]
                          for j in range(K)))
    heads = lambda x: x.reshape(B, T, H, D)                  # noqa: E731
    unit = lambda x: x * jax.lax.rsqrt(                      # noqa: E731
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
    q = unit(heads(act[..., :HD])) * jnp.float32(D) ** -0.5
    k = unit(heads(act[..., HD:2 * HD]))
    v = heads(act[..., 2 * HD:])
    rate = jnp.exp(_f32(params[f"{p}_kda_A_log"]))[:, None]  # (H, 1)
    g = jnp.float32(cfg["kda_lower_bound"]) * jax.nn.sigmoid(
        rate * heads(wide[..., 3 * HD:4 * HD]
                     + _f32(params[f"{p}_kda_dt_bias"])))    # log a
    beta = jax.nn.sigmoid(wide[..., 5 * HD:])                # (B, T, H)
    keep = _rounder(state_dtype)
    kept = jnp.ones((T,), jnp.float32) if state_every is None \
        else _f32(jnp.arange(T) % state_every != 0)

    def step(s, row):
        q_t, k_t, v_t, g_t, b_t, m_t = row     # (B,H,D) x4, (B,H), ()
        sd = jnp.exp(g_t)[..., None] * (m_t * s)
        delta = b_t[..., None] * (v_t - jnp.sum(k_t[..., None] * sd, axis=2))
        s = keep(sd + k_t[..., None] * delta[:, :, None, :])
        return s, jnp.sum(q_t[..., None] * s, axis=2)

    swap = lambda a: jnp.swapaxes(a, 0, 1)                   # noqa: E731
    _, o = jax.lax.scan(step, jnp.zeros((B, H, D, D), jnp.float32),
                        (swap(q), swap(k), swap(v), swap(g), swap(beta),
                         kept))
    o = _rms_norm(swap(o), params[f"{p}_kda_norm_weight"],
                  cfg["rms_norm_eps"])
    return o.reshape(B, T, HD) * jax.nn.sigmoid(wide[..., 4 * HD:5 * HD])


def attention(a, p, params, cfg, rd, block):
    """The un-absorbed latent attention of rows ``a`` (B, T, hidden)
    over every j <= t with the direct query and the head-wise gate: (B,
    T, H * v_head_dim) before ``W_o``."""
    B, T, _ = a.shape
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    inv_freq, trig, scale, _ = yarn(cfg)        # rope_scaling null: plain
    q = _dense(a, params[f"{p}_q_weight"], rd).reshape(B, T, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope(q[..., dn:], inv_freq, trig)
    kv = _dense(a, params[f"{p}_kv_a_weight"], rd)
    c_kv = _rms_norm(kv[..., :rank], params[f"{p}_attn_kv_norm_weight"],
                     cfg["rms_norm_eps"])
    k_r = _rope(kv[..., rank:], inv_freq, trig)                # (B, T, dr)
    kvb = _dense(c_kv, params[f"{p}_attn_kv_b_weight"], rd) \
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

    out = _by_query_block(rows, T, block, q_n, q_r)            # (B,T,H,dv)
    gate = jax.nn.sigmoid(_dense(a, params[f"{p}_gate_weight"], rd))
    return (out * gate[..., None]).reshape(B, T, H * dv)


def expert_layer(m, p, params, cfg, rd, held=None):
    """The sparse feed-forward of rows ``m`` (N, hidden): the held
    experts' part (``held`` = (first, count), default the
    configuration's) of every row's weighted sum, one expert at a time,
    and the shared expert: ``(routed, shared, chosen (N, k))``."""
    first, count = held or (cfg.get("held_first", 0),
                            cfg.get("num_experts_held", cfg["num_experts"]))
    sc = jax.nn.sigmoid(_dense(m, params[f"{p}_moe_router_weight"], rd))
    chosen, weight = route(sc, params[f"{p}_moe_router_bias"], cfg)

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


def forward(params, tokens, config, name="lm", round_to=None,
            state_dtype=None, state_every=None, tail=None, block=128,
            head_blocks=8, return_chosen=False):
    """Logits (B, T, vocab held) - or, with ``tail=n``, (B, n, vocab
    held) of the last n positions - of ``tokens`` (B, T) int32 under
    ``params`` ({program name: array}), float32 at the highest matmul
    precision (module docstring for the switches). ``return_chosen``
    adds the routed experts of every sparse layer, (layers, B * T, k)."""
    cfg, eps = config, config["rms_norm_eps"]
    hidden = cfg["hidden_size"]
    B, T = tokens.shape
    rd = _rounder(round_to)
    choices = []
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.asarray(params[f"{name}_tok_embed_weight"])[tokens])
        for i, kind in enumerate(layer_types(cfg)):
            p = f"{name}_l{i}"
            n = _rms_norm(x, params[f"{p}_ln1_gamma"], eps)
            mixed = kda(n, p, params, cfg, rd, state_dtype, state_every) \
                if kind == "kda" \
                else attention(n, p, params, cfg, rd, min(block, T))
            x = x + _dense(mixed, params[f"{p}_proj_weight"], rd)
            m = _rms_norm(x, params[f"{p}_ln2_gamma"], eps)
            if i < cfg["first_k_dense_replace"]:
                F = cfg["intermediate_size"]
                w = _f32(params[f"{p}_ffn_gate_up_weight"])
                x = x + _gated(m, w[:F].T, w[F:].T,
                               _f32(params[f"{p}_ffn_down_weight"]).T, rd)
            else:
                routed, shared, chosen = expert_layer(
                    m.reshape(B * T, hidden), p, params, cfg, rd)
                choices.append(chosen)
                x = x + (routed + shared).reshape(B, T, hidden)
        if tail is not None:
            x = x[:, T - tail:]
        x = rd(_rms_norm(x, params[f"{name}_ln_f_gamma"], eps))
        head = jnp.asarray(params[f"{name}_head_weight"])
        V = head.shape[0]
        blocks = head_blocks if V % head_blocks == 0 else 1
        parts = jax.lax.map(lambda w: x @ rd(_f32(w)).T,
                            head.reshape(blocks, V // blocks, -1))
        logits = jnp.moveaxis(parts, 0, 2).reshape(x.shape[:2] + (V,))
    if return_chosen:
        return logits, jnp.stack(choices)
    return logits
