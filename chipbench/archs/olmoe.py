"""Architecture "olmoe": ``models/transformer.py``'s sparse-expert
decoder (``block="olmoe"``: RMSNorm, fused q/k/v without bias, RMSNorm
of the whole q and k projections, rotary positions, ``MoEFFN`` - the
top-k of ``num_experts`` gated-SiLU feed-forwards -, an untied head, an
unscaled embedding), served through ``serve_decoder``. The ``serve``
interface of chipbench/README.md; the configuration's keys are the
published config.json's."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.reference import olmoe as _reference

#: |served - reference| <= TOL + TOL * |reference| on every compared
#: logit. The served path holds its parameters in bfloat16 and computes
#: in bfloat16 (8 significant bits, relative step 2**-8 = 0.004) with
#: float32 accumulation through 8 layers; the reference upcasts the
#: same parameters and computes in float32 at the highest matmul
#: precision. Routing is discontinuous: a (token, layer) whose 8th and
#: 9th router probabilities lie within bfloat16's rounding goes to
#: another expert than in the reference, and the served logits then
#: differ by that expert's share of the output, not by rounding alone.
#: So the bound is set from readings, not from the step size.
#: Measured on the v5e at the published widths (my chip runs, PR 28;
#: PERF.md, Findings): the served path's max error 0.046-0.076 on
#: logits of magnitude up to 4.7 over 14 seeds, 0.25-0.39 of this bound
#: at the worst element; the reference's own bfloat16-operand emulation
#: of the served path reads 0.057-0.080 against float32, with 3.2-4.4 %
#: of the (layer, sequence, position) routing decisions choosing
#: another set of experts. The control - the same reference with every
#: matmul operand rounded to float8_e4m3fn (3 significant bits, the
#: nearest precision below the one the configuration states) - reads
#: 0.58-0.68: 3.2-3.7 times the bound, not correct. Every run prints
#: both on its ``reference_detail`` line.
LOGIT_TOL = 0.16


def _kwargs(cfg):
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("clip_qkv") \
            or cfg.get("rope_scaling") or cfg.get("attention_bias"):
        raise SystemExit("chipbench: archs/olmoe.py builds the published "
                         "block: silu, no clip_qkv, no rope scaling, no "
                         "attention bias")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise SystemExit("chipbench: archs/olmoe.py: every head has its "
                         "own keys and values in this configuration")
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], pos_embed="rotary",
        rope_base=float(cfg["rope_theta"]), block="olmoe",
        n_expert=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        expert_width=cfg["intermediate_size"],
        norm_topk=bool(cfg["norm_topk_prob"]),
        rms_eps=cfg["rms_norm_eps"],
        tie_head=bool(cfg["tie_word_embeddings"]), embed_scale=False)


def decode_symbol(cfg, step_len):
    """The program's own builder call. Called before any weight is
    drawn, so a tree whose ``models/transformer.py`` lacks the block
    fails here, at once (TypeError: unexpected keyword ``block``)."""
    from mxnet_tpu.models import transformer as tfm
    return tfm.get_decode_symbol(
        capacity=cfg["capacity"], per_slot=True, step_len=step_len,
        max_seq_len=cfg["max_position_embeddings"], **_kwargs(cfg))


def data_shapes(cfg, slots, step_len):
    return {"data": (slots, step_len)}              # rotary: no pos_ids


def make_params(symbol, data_shapes, seed, cfg):
    """At the dtype the configuration states (bfloat16): what a
    checkpoint of this model is, and what ``DecodeEngine`` then binds
    without a float32 master."""
    return weights.normal_init(symbol, data_shapes, seed,
                               dtype=cfg["param_dtype"])


def _say(**fields):
    print(json.dumps({"chipbench": "reference_detail", **fields}),
          flush=True)


def _report(flip_share, emu_err, ctrl_err, ctrl_over):
    _say(routing_flip_share=float(flip_share),
         routing_compared="float32 reference against its own "
         "bfloat16-operand emulation of the served path, share of "
         "(layer, sequence, position) decisions with another expert set",
         bfloat16_emulation_max_abs_err=float(emu_err),
         control="the reference with every matmul operand rounded to "
         "float8_e4m3fn",
         control_max_abs_err=float(ctrl_err),
         control_max_err_over_bound=float(ctrl_over),
         control_correct=bool(ctrl_over <= 1.0), tolerance=LOGIT_TOL)
    return np.float32(0.0)


def reference_logits(params, tokens, cfg):
    """The plain reference's logits. Beside them, on a line of its own
    (``reference_detail``), what the comparison cannot say by itself:
    the share of routing decisions that move under bfloat16 operands,
    and the control - the reference at float8 operands - against the
    same bound, which has to come out not correct."""
    want, chosen = _reference.forward(params, tokens, cfg,
                                      return_routing=True)
    emu, emu_chosen = _reference.forward(
        params, tokens, cfg, round_to=jnp.bfloat16, return_routing=True)
    ctrl = _reference.forward(params, tokens, cfg,
                              round_to=jnp.float8_e4m3fn)
    bound = LOGIT_TOL + LOGIT_TOL * jnp.abs(want)
    ctrl_err = jnp.abs(ctrl - want)
    # the line is printed before the logits are handed back: the
    # callback's result is part of them
    zero = jax.experimental.io_callback(
        _report, jax.ShapeDtypeStruct((), jnp.float32),
        _reference.routing_flip_share(chosen, emu_chosen),
        jnp.max(jnp.abs(emu - want)), jnp.max(ctrl_err),
        jnp.max(ctrl_err / bound), ordered=True)
    return want + zero


# ------------------------------------------------------------------ costs
def moe_expert_bytes(cfg):
    """One expert's three matrices at the stated parameter width."""
    width = 2 if cfg["param_dtype"] == "bfloat16" else 4
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"] * width


def experts_touched(cfg, tokens):
    """Expected number of experts with at least one of ``tokens``
    tokens' assignments under EVEN routing: E * (1 - (1 - k/E)**tokens).
    ``costs`` sees no counter; the measured count is the per-layer
    metric ``moe.experts_touched_per_layer_step``."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** tokens)


def step(cfg, slots, step_len, live_rows):
    """One dispatch of the slot-pooled decode program: ``slots`` rows
    of ``step_len`` tokens, each slot attending ``live_rows`` cached
    positions (mean over slots). What the algorithm needs at the stated
    width: attention, router and head weights once, the experts touched
    (even-routing expectation) once, live cache rows, the new cache
    rows, float32 logits out. Pads count as tokens."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], \
        cfg["vocab_size"]
    E, k, F = cfg["num_experts"], cfg["num_experts_per_tok"], \
        cfg["intermediate_size"]
    width = 2 if cfg["param_dtype"] == "bfloat16" else 4
    tokens = slots * step_len
    dense = 4 * d * d + E * d                 # q, k, v, o and the router
    touched = experts_touched(cfg, tokens)
    matmul = 2 * tokens * (L * (dense + k * 3 * d * F) + V * d)
    attn = 4 * tokens * (live_rows + step_len / 2.0) * d * L
    kv_row = 2 * d * L * width                # K and V, every layer
    return {"flops": matmul + attn,
            "bytes": (L * dense + V * d) * width          # dense weights
            + L * touched * moe_expert_bytes(cfg)         # experts touched
            + tokens * d * width                          # embedding rows
            + slots * live_rows * kv_row                  # cache read
            + tokens * kv_row                             # cache write
            + tokens * V * 4,                             # logits out
            "experts_touched_per_layer": touched}


def costs(cfg, slots, step_len, live_rows):
    return {"decode_step": step(cfg, slots, 1, live_rows),
            "window_step": step(cfg, slots, step_len, live_rows),
            "moe_expert": {"flops": 0.0, "bytes": moe_expert_bytes(cfg)}}
