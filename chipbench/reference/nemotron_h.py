"""Plain reference: the Nemotron-H decoder (``model_type nemotron_h``:
NVIDIA-Nemotron-3-Nano-30B-A3B) in float32 jax.numpy - one full-sequence
forward, the recurrence step by step with B and C indexed by group, the
router as published, one expert at a time, no chunks, no cache, no
kernels.

The published description: nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16's
config.json (catalog row ``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` of
model-configs/architectures.jsonl), the Nemotron-H report
(arXiv:2504.03624) and the Mamba-2 paper (arXiv:2405.21060); the
modelling file (``modeling_nemotron_h.py``) is not on this machine, so
what the config leaves open is read from those and listed below. ``x0 =
E[token]`` (unscaled); **a layer is one sub-layer**, by its letter of
``hybrid_override_pattern`` at ``layers_run``, ``N`` = RMSNorm(eps
``layer_norm_epsilon``, a gain), no bias anywhere but the
convolution's:

    x = x + Mixer_i(N_i(x))        Mixer_i: ``M``, ``*`` or ``E``

then ``logits = N_f(x) W_head^T`` (an untied head).

**M, Mamba-2**, ``H = mamba_num_heads`` heads of ``P = mamba_head_dim``
(``d_in = H P``, which is not ``expand x hidden_size``), ``N =
ssm_state_size``, ``G = n_groups`` groups of B and C, per token ``t``
and head ``h`` of group ``g(h) = h // (H / G)``:

    [z_t | xBC_t | dt_t] = W_in u_t          d_in | d_in + 2 G N | H
    c_t   = silu(sum_{k<K} w_conv[:, k] xBC_{t-(K-1)+k} + b_conv)
    [x_t | B_t | C_t] = c_t                  B_t, C_t: (G, N)
    dlt_t = softplus(dt_t + dt_bias)         a_t = exp(dlt_t A), A = -exp(A_log)
    H_t[h] = a_t[h] H_{t-1}[h] + dlt_t[h] x_t[h] (outer) B_t[g(h)], H_{-1} = 0
    y_t[h] = H_t[h] C_t[g(h)] + D[h] x_t[h]
    o_t   = W_out(w_norm * rmsnorm_group(y_t * silu(z_t)))

``rmsnorm_group``: the statistic over each group's ``d_in / G``
channels by itself, one gain of ``d_in``.

**\\*, attention**: q of ``num_attention_heads`` heads, k and v of
``num_key_value_heads`` heads of ``head_dim`` (a key of its own), no
bias, **no positions**, scores ``q k^T / sqrt(head_dim)``, causal
softmax, each K/V head read by ``heads / kv_heads`` consecutive query
heads, then ``W_o``.

**E, experts**: ``s = sigmoid(float32(W_r u))`` over
``n_routed_experts``; the ``num_experts_per_tok`` largest of ``s +
e_score_correction_bias`` are chosen (``n_group`` 1, ``topk_group`` 1:
among all); their weights are ``s`` at the chosen, divided by their sum
(``norm_topk_prob``), times ``routed_scaling_factor`` - the bias steers
the choice and never the weights; an expert is ``W_down relu(W_up
u)^2`` (``mlp_hidden_act relu2``, two matrices, no gate); plus one
shared expert of the same form at
``moe_shared_expert_intermediate_size`` over every token.

**A chip's share.** The configuration holds ``n_routed_experts_held``
of the ``n_routed_experts`` experts from ``held_first`` on (expert
parallelism without its exchange): the router keeps its published width
and every token is routed over all experts, the weights keep their
normalisation over the chosen, and the sum runs over the chosen experts
that are held here alone. What an absent expert would add is left out,
here as in the program, and that partial result goes on to the next
layer. The vocabulary is a slice likewise: embedding and head have
``vocab_size`` rows, ids and logits are over them.

Readings the config leaves open (each also under the configuration's
``assumed``):
  * the attention takes no rotary: ``nemotron_h``'s attention is
    Jamba's (the Nemotron-H report: no positional embedding, the Mamba-2
    layers carry order); ``rope_theta`` and ``partial_rotary_factor``
    are kept as published and read by nothing;
  * the projection's split order is ``z | xBC | dt`` and the
    convolution's channels ``x | B | C``, groups consecutive inside B
    and C (the Mamba-2 code);
  * the gate ``silu(z)`` is applied BEFORE the norm's statistic, the
    statistic over a group's channels (the Mamba-2 code's
    ``RMSNormGated(norm_before_gate=False, group_size=d_in / G)``);
  * ``dt`` has no limits beyond softplus (``time_step_limit`` (0, inf));
    ``time_step_*`` and ``rescale_prenorm_residual`` are initialisation;
  * ``D`` is one scalar a head;
  * the router's logits and sigmoid are float32; equal choice scores
    choose the expert of lowest index.

Departures from the published code, in parameter LAYOUT only, following
models/transformer.py, ops/ssm.py and ops/moe.py (the program under
test): q, k and v are the row blocks of one ``*_qkv_weight``; the
mixer's ``W_in`` is ``*_mamba_in_weight``, its convolution
``*_mamba_conv_weight`` (channels, taps) and ``*_mamba_conv_bias``,
``*_mamba_dt_bias``, ``*_mamba_A_log``, ``*_mamba_D``, the gated norm's
gain ``*_mamba_norm_gamma``; both mixers' output projection is
``*_proj_weight``; the router is ``*_moe_router_weight`` (experts,
hidden) with ``*_moe_router_bias`` (the published
``e_score_correction_bias``); the held experts are stacked,
``*_moe_up_weight`` (held, width, hidden: ``up_proj.weight`` as
published) and ``*_moe_down_weight`` (held, width, hidden:
``down_proj.weight`` transposed); the shared expert
``*_moe_shared_up_weight`` (hidden, shared) and
``*_moe_shared_down_weight`` (shared, hidden). An ``M`` or ``*`` layer's
norm is ``*_ln1_gamma``, an ``E`` layer's ``*_ln2_gamma``.

Controls (``forward``'s switches): ``round_to=`` rounds every matmul
operand to that dtype first (the nearest precision below the stated
bfloat16 is float8_e4m3fn); ``state_every=n`` drops the state that a
token takes over at every token whose index is a multiple of n (1: a
state that carries nothing from one token to the next); ``routed=False``
leaves the routed experts' sum out of every ``E`` layer (the shared
expert alone); ``act="relu"`` replaces relu squared by relu;
``scaled=False`` leaves ``routed_scaling_factor`` out of the weights;
``choice_bias=False`` leaves the correction bias out of the choice;
``one_group=True`` lets every head read group 0's B and C;
``group_norm=False`` takes the gated norm's statistic over all ``d_in``
channels. ``tail=n`` returns the logits of the last n positions alone,
the head a block of the vocabulary at a time. Parameters are taken by
the program's names and upcast where they are used, a layer - and
inside it an expert - at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.axk1 import choice_flip_share
from chipbench.reference.granite_hybrid import (_dense, _f32, _rms_norm,
                                                _rounder)

__all__ = ["forward", "layer_kinds", "route", "expert_layer", "mamba",
           "attention", "choice_flip_share"]


def layer_kinds(cfg):
    """``"M"``, ``"*"`` or ``"E"`` for each layer that is run: the
    published ``hybrid_override_pattern`` at ``layers_run`` (default:
    all)."""
    pattern = cfg["hybrid_override_pattern"]
    return [pattern[i] for i in cfg.get("layers_run", range(len(pattern)))]


def attention(n, p, params, cfg, rd):
    """Grouped attention without positions (module docstring): ``n`` (B,
    T, D) -> (B, T, heads x head_dim) before ``W_o``."""
    B, T, _ = n.shape
    heads, kv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    qkv = _dense(n, params[f"{p}_qkv_weight"], rd)
    q = qkv[..., :heads * dh].reshape(B, T, kv, heads // kv, dh)
    k = qkv[..., heads * dh:(heads + kv) * dh].reshape(B, T, kv, dh)
    v = qkv[..., (heads + kv) * dh:].reshape(B, T, kv, dh)
    s = jnp.einsum("bqcgd,bkcd->bcgqk", rd(q), rd(k)) \
        / jnp.sqrt(jnp.float32(dh))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None, None], s, -jnp.inf)
    a = jnp.einsum("bcgqk,bkcd->bqcgd", rd(jax.nn.softmax(s, axis=-1)),
                   rd(v))
    return a.reshape(B, T, heads * dh)


def mamba(n, p, params, cfg, rd, state_every=None, one_group=False,
          group_norm=True):
    """The Mamba-2 mixer (module docstring), the recurrence one token
    at a time, B and C by indexing ``h // (H / G)``: ``n`` (B, T, D) ->
    (B, T, d_in) before ``W_out``."""
    B, T, _ = n.shape
    H, P, N, K, G = (cfg[k] for k in (
        "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "conv_kernel",
        "n_groups"))
    d_in = H * P
    C = d_in + 2 * G * N
    wide = _dense(n, params[f"{p}_mamba_in_weight"], rd)
    z, xbc, dt = wide[..., :d_in], wide[..., d_in:d_in + C], \
        wide[..., d_in + C:]
    w = _f32(params[f"{p}_mamba_conv_weight"])                 # (C, K)
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + T] * w[None, None, :, k] for k in range(K))
    c = jax.nn.silu(conv + _f32(params[f"{p}_mamba_conv_bias"]))
    x = c[..., :d_in].reshape(B, T, H, P)
    # head h reads group h // (H / G); the control reads group 0
    group = jnp.zeros((H,), jnp.int32) if one_group \
        else jnp.arange(H) // (H // G)
    Bm = c[..., d_in:d_in + G * N].reshape(B, T, G, N)[:, :, group]
    Cm = c[..., d_in + G * N:].reshape(B, T, G, N)[:, :, group]
    dlt = jax.nn.softplus(dt + _f32(params[f"{p}_mamba_dt_bias"]))  # (B, T, H)
    A = -jnp.exp(_f32(params[f"{p}_mamba_A_log"]))
    kept = jnp.ones((T,), jnp.float32) if state_every is None \
        else _f32(jnp.arange(T) % state_every != 0)

    def step(h, row):
        x_t, d_t, b_t, c_t, m_t = row   # (B,H,P) (B,H) (B,H,N) (B,H,N) ()
        h = jnp.exp(d_t * A)[:, :, None, None] * (m_t * h) \
            + (d_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.sum(h * c_t[:, :, None, :], axis=-1)

    swap = lambda a: jnp.swapaxes(a, 0, 1)                   # noqa: E731
    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), jnp.float32),
                        (swap(x), swap(dlt), swap(Bm), swap(Cm), kept))
    y = swap(y) + _f32(params[f"{p}_mamba_D"])[None, None, :, None] * x
    gated = y.reshape(B, T, d_in) * jax.nn.silu(z)
    gamma, eps = params[f"{p}_mamba_norm_gamma"], cfg["layer_norm_epsilon"]
    if not group_norm:                  # the control: one statistic
        return _rms_norm(gated, gamma, eps)
    parts = gated.reshape(B, T, G, d_in // G)
    var = jnp.mean(jnp.square(parts), axis=-1, keepdims=True)
    return (parts * jax.lax.rsqrt(var + eps)).reshape(B, T, d_in) \
        * _f32(gamma)


def route(logits, bias, k, scale, choice_bias=True, scaled=True):
    """The published router over float32 ``logits`` (N, E): scores
    ``sigmoid(logits)``, the ``k`` largest of ``scores + bias`` chosen,
    their weights the scores themselves over their sum, times
    ``scale``: ``(chosen (N, k) int32; weight (N, E) float32, 0 off the
    chosen)``. Controls: ``choice_bias=False`` chooses by the scores
    alone, ``scaled=False`` leaves ``scale`` out."""
    N, E = logits.shape
    score = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(score + _f32(bias) if choice_bias else score,
                              k)
    w = jnp.take_along_axis(score, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    if scaled:
        w = w * jnp.float32(scale)
    weight = jnp.zeros((N, E), jnp.float32) \
        .at[jnp.arange(N)[:, None], chosen].set(w)
    return chosen.astype(jnp.int32), weight


def _ungated(h, up, down, rd, act):
    """``down(act(up h))``: ``up`` (width, hidden) as published,
    ``down`` (width, hidden) transposed; relu squared, or the control's
    relu."""
    a = jnp.maximum(rd(h) @ rd(_f32(up)).T, 0.0)
    return rd(jnp.square(a) if act == "relu2" else a) @ rd(_f32(down))


def expert_layer(h, p, params, cfg, rd, held=None, act="relu2",
                 choice_bias=True, scaled=True):
    """The sparse feed-forward of rows ``h`` (N, hidden): the held
    experts' part (``held`` = (first, count), default the
    configuration's) of every row's weighted sum, one expert at a time,
    and the shared expert: ``(routed, shared, chosen (N, k))``."""
    E = cfg["n_routed_experts"]
    first, count = held or (cfg.get("held_first", 0),
                            cfg.get("n_routed_experts_held", E))
    chosen, weight = route(
        _dense(h, params[f"{p}_moe_router_weight"], rd),
        params[f"{p}_moe_router_bias"], cfg["num_experts_per_tok"],
        cfg["routed_scaling_factor"], choice_bias, scaled)

    def one_expert(acc, xs):
        up, down, w = xs
        return acc + w[:, None] * _ungated(h, up, down, rd, act), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (params[f"{p}_moe_up_weight"], params[f"{p}_moe_down_weight"],
         weight.T[first:first + count]))
    shared = _ungated(h, _f32(params[f"{p}_moe_shared_up_weight"]).T,
                      params[f"{p}_moe_shared_down_weight"], rd, act)
    return routed, shared, chosen


def forward(params, tokens, config, name="lm", round_to=None,
            state_every=None, routed=True, act="relu2", scaled=True,
            choice_bias=True, one_group=False, group_norm=True, tail=None,
            head_blocks=8, return_chosen=False):
    """Logits (B, T, vocab held) - or, with ``tail=n``, (B, n, vocab
    held) of the last n positions - of ``tokens`` (B, T) int32 under
    ``params`` ({program name: array}), in float32 at the highest matmul
    precision (module docstring for the switches). ``return_chosen``
    adds the routed experts of every ``E`` layer, (E layers, B * T,
    k)."""
    cfg, eps = config, config["layer_norm_epsilon"]
    B, T = tokens.shape
    hidden = cfg["hidden_size"]
    rd = _rounder(round_to)
    choices = []
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.asarray(params[f"{name}_tok_embed_weight"])[tokens])
        for i, kind in enumerate(layer_kinds(cfg)):
            p = f"{name}_l{i}"
            if kind == "E":
                h = _rms_norm(x, params[f"{p}_ln2_gamma"], eps)
                y, s, chosen = expert_layer(
                    h.reshape(B * T, hidden), p, params, cfg, rd, act=act,
                    choice_bias=choice_bias, scaled=scaled)
                choices.append(chosen)
                x = x + ((y if routed else 0.0) + s).reshape(B, T, hidden)
                continue
            n = _rms_norm(x, params[f"{p}_ln1_gamma"], eps)
            mixed = mamba(n, p, params, cfg, rd, state_every, one_group,
                          group_norm) \
                if kind == "M" else attention(n, p, params, cfg, rd)
            x = x + _dense(mixed, params[f"{p}_proj_weight"], rd)
        if tail is not None:
            x = x[:, T - tail:]
        x = rd(_rms_norm(x, params[f"{name}_ln_f_gamma"], eps))
        head = params[f"{name}_head_weight"]
        V = head.shape[0]
        blocks = head_blocks if V % head_blocks == 0 else 1
        parts = jax.lax.map(
            lambda block: x @ rd(_f32(block)).T,
            jnp.asarray(head).reshape(blocks, V // blocks, -1))
        logits = jnp.moveaxis(parts, 0, 2).reshape(x.shape[:2] + (V,))
    if return_chosen:
        return logits, jnp.stack(choices)
    return logits
