"""Plain reference: the Granite 4.0-H decoder WITH routed experts
(``model_type granitemoehybrid``, ``num_local_experts`` > 0: Granite
4.0-H Small) in float32 jax.numpy - one full-sequence forward, the
recurrence step by step, the router as published, one expert at a time,
no chunks, no cache, no kernels.

The published description: ibm-granite/granite-4.0-h-small's
config.json (catalog row ``granite-4.0-h-small`` of model-configs/
architectures.jsonl) and the model code of ``transformers``'
``modeling_granitemoehybrid.py`` (``GraniteMoeHybridMoE``,
``GraniteMoeHybridTopKGating``, ``GraniteMoeHybridParallelExperts``,
``GraniteMoeHybridMLP`` as the shared feed-forward). ``x0 =
embedding_multiplier E[token]``; for each layer ``i`` with ``m =
residual_multiplier`` and ``N`` = RMSNorm(eps ``rms_norm_eps``, a gain):

    x = x + m Mixer_i(N1(x))                 Mixer_i by layer_types[i]:
                                             reference/granite_hybrid.py's
                                             ``mamba`` or ``attention``
    h = N2(x)
    l = h Wr^T                               num_local_experts logits
    (v, e) = top_k(l), k = num_experts_per_tok
    g = softmax(v)                           over the CHOSEN logits alone
    y = sum_j g_j Wo[e_j](silu(a_j) * b_j),  [a_j | b_j] = Wi[e_j] h
    s = Ws_o(silu(p) * q),                   [p | q] = Ws_i h
    x = x + m (y + s)

``logits = (N(x) E^T) / logits_scaling`` (tied head). The layers run
are the published ones at ``layers_run`` (a cut in depth: the first
period of ten, layers 0-9, attention at 5), numbered anew from 0.

**A chip's share.** The configuration holds ``num_experts_held`` of the
``num_local_experts`` experts from ``held_first`` on (expert parallelism
without its exchange): the router keeps its published width and every
token is routed over all experts, ``g`` keeps its normalisation over
the ``k`` chosen, and the sum ``y`` runs over the chosen experts that
are held here alone. What an absent expert would add is left out, here
as in the program, and that partial result goes on to the next layer.
The vocabulary is a slice likewise: the embedding has ``vocab_size``
rows, ids and logits are over them.

Readings the config leaves open (each also under the configuration's
``assumed``), as the published model code has them:
  * ``intermediate_size`` is the width of ONE routed expert (the config
    has no key of its own for it; the catalog notes the inference) and
    ``shared_intermediate_size`` that of the shared feed-forward, which
    every token passes and whose output is added to the routed one
    before ``residual_multiplier``;
  * the router has no bias, no noise and no auxiliary term at
    inference; it takes the top-k of the LOGITS first and the softmax
    over those k in float32 - equal to the softmax over all experts cut
    to its k largest and renormalised, which is what the program's
    ``MoEFFN(norm_topk=True)`` computes;
  * an expert is a gated SiLU whose gate and up are the halves of one
    input projection (``a | b``);
  * the Mamba-2 mixer and the attention as reference/granite_hybrid.py's
    head lists them (split order, convolution, ``dt`` limits, the gated
    norm over all ``d_in`` numbers, ``D`` a head, the four multipliers).

Departures in parameter LAYOUT only, following models/transformer.py
and ops/moe.py (the program under test): the router is
``*_moe_router_weight`` (experts, hidden); the held experts' gate, up
and down are ``*_moe_gate_weight`` / ``*_moe_up_weight`` (held, hidden,
width) and ``*_moe_down_weight`` (held, width, hidden), K-major and
apart; the shared feed-forward ``*_moe_shared_gate_weight`` /
``*_moe_shared_up_weight`` (hidden, shared) and
``*_moe_shared_down_weight`` (shared, hidden); the mixers' as
reference/granite_hybrid.py has them.

Controls: ``round_to=``, ``state_dtype=``, ``state_every=`` and
``tail=`` are reference/granite_hybrid.py's (operands rounded, the
state rounded, the state dropped, the last positions alone);
``routed=False`` leaves the routed experts' sum out of every layer (the
shared feed-forward alone); ``renorm=False`` weighs the chosen experts
by the softmax over ALL experts, not renormalised over the chosen.
Parameters are taken by the program's names and upcast where they are
used, a layer - and inside it an expert - at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.axk1 import _gated, choice_flip_share
from chipbench.reference.granite_hybrid import (_dense, _f32, _rms_norm,
                                                _rounder, attention, mamba)

__all__ = ["forward", "layer_types", "route", "expert_layer",
           "choice_flip_share"]


def layer_types(cfg):
    """``"mamba"`` or ``"attention"`` for each layer that is run: the
    published ``layer_types`` at ``layers_run`` (default: all)."""
    kinds = cfg["layer_types"]
    return [kinds[i] for i in cfg.get("layers_run", range(len(kinds)))]


def route(logits, k, renorm=True):
    """The published router over ``logits`` (N, E) float32: the ``k``
    largest logits, a softmax over those ``k``: ``(chosen (N, k) int32,
    largest first; weight (N, E) float32, 0 off the chosen)``. With
    ``renorm=False`` (a control) the weights are the softmax over all
    ``E`` at the chosen, not renormalised."""
    N, E = logits.shape
    v, chosen = jax.lax.top_k(logits, k)
    g = jax.nn.softmax(v, axis=-1) if renorm \
        else jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), chosen,
                                 axis=-1)
    weight = jnp.zeros((N, E), jnp.float32) \
        .at[jnp.arange(N)[:, None], chosen].set(g)
    return chosen.astype(jnp.int32), weight


def expert_layer(h, p, params, cfg, rd, held=None, renorm=True):
    """The sparse feed-forward of rows ``h`` (N, hidden): the held
    experts' part (``held`` = (first, count), default the
    configuration's) of every row's weighted sum, one expert at a time,
    and the shared feed-forward: ``(routed, shared, chosen (N, k))``."""
    E = cfg["num_local_experts"]
    first, count = held or (cfg.get("held_first", 0),
                            cfg.get("num_experts_held", E))
    chosen, weight = route(_dense(h, params[f"{p}_moe_router_weight"], rd),
                           cfg["num_experts_per_tok"], renorm)

    def one_expert(acc, xs):
        gate, up, down, w = xs
        return acc + w[:, None] * _gated(h, gate, up, down, rd), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (params[f"{p}_moe_gate_weight"], params[f"{p}_moe_up_weight"],
         params[f"{p}_moe_down_weight"], weight.T[first:first + count]))
    shared = _gated(h, params[f"{p}_moe_shared_gate_weight"],
                    params[f"{p}_moe_shared_up_weight"],
                    params[f"{p}_moe_shared_down_weight"], rd)
    return routed, shared, chosen


def forward(params, tokens, config, name="lm", round_to=None,
            state_dtype=None, state_every=None, routed=True, renorm=True,
            tail=None, head_blocks=8, return_chosen=False):
    """Logits (B, T, vocab held) - or, with ``tail=n``, (B, n, vocab
    held) of the last n positions - of ``tokens`` (B, T) int32 under
    ``params`` ({program name: array}), in float32 at the highest matmul
    precision (module docstring for the switches). ``return_chosen``
    adds the routed experts of every layer, (layers, B * T, k)."""
    cfg, eps = config, config["rms_norm_eps"]
    B, T = tokens.shape
    hidden = cfg["hidden_size"]
    m = jnp.float32(cfg["residual_multiplier"])
    rd = _rounder(round_to)
    choices = []
    with jax.default_matmul_precision("highest"):
        emb = params[f"{name}_tok_embed_weight"]
        x = _f32(jnp.asarray(emb)[tokens]) \
            * jnp.float32(cfg["embedding_multiplier"])
        for i, kind in enumerate(layer_types(cfg)):
            p = f"{name}_l{i}"
            n = _rms_norm(x, params[f"{p}_ln1_gamma"], eps)
            mixed = mamba(n, p, params, cfg, rd, state_dtype, state_every) \
                if kind == "mamba" else attention(n, p, params, cfg, rd)
            x = x + m * _dense(mixed, params[f"{p}_proj_weight"], rd)
            h = _rms_norm(x, params[f"{p}_ln2_gamma"], eps)
            y, s, chosen = expert_layer(h.reshape(B * T, hidden), p, params,
                                        cfg, rd, renorm=renorm)
            choices.append(chosen)
            x = x + m * ((y if routed else 0.0) + s).reshape(B, T, hidden)
        if tail is not None:
            x = x[:, T - tail:]
        x = rd(_rms_norm(x, params[f"{name}_ln_f_gamma"], eps))
        V = emb.shape[0]
        blocks = head_blocks if V % head_blocks == 0 else 1
        parts = jax.lax.map(
            lambda block: x @ rd(_f32(block)).T,
            jnp.asarray(emb).reshape(blocks, V // blocks, -1))
        logits = jnp.moveaxis(parts, 0, 2).reshape(x.shape[:2] + (V,)) \
            / jnp.float32(cfg["logits_scaling"])
    if return_chosen:
        return logits, jnp.stack(choices)
    return logits
