"""Architecture "evabyte": ``models/transformer.py``'s EvaByte decoder
(``block="evabyte"``: RMSNorm with a unit offset, fused q/k/v without
bias, rotary positions, EVA chunked linearized attention with its state
- ``ops/eva.py``: the open window's exact rows beside one summary per
chunk of everything older -, a dense gated-SiLU feed-forward, a float32
residual stream, an untied head of ``num_pred_heads`` blocks of
``vocab_size`` columns), served through ``serve_decoder``. The
``serve`` interface of chipbench/README.md; the configuration's keys
are the published config.json's."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import evabyte as _reference

#: |served - reference| <= TOL + TOL * |reference| on every compared
#: logit (head 0: the next byte). The served path holds parameters and
#: state in bfloat16 and multiplies in bfloat16 (8 significant bits)
#: with float32 accumulation, a float32 residual stream and float32
#: softmax statistics, through 8 layers of width 4096; the reference
#: upcasts the same parameters and computes in float32 at the highest
#: matmul precision. Set from two readings on the v5e at the published
#: widths (my chip runs, PR 32; PERF.md, Findings). The served path's
#: largest error over 11 seeds: 0.104-0.141 on logits of magnitude up to
#: 5.0-6.1 over check_reference's 2,064 positions, 0.148 and 0.159 on
#: logits up to 6.6 over the long comparison's 4,200 (two seeds of
#: tests/evabyte_long.py): 0.23-0.37 of this bound at the worst
#: element; the reference's own bfloat16-operand emulation of the
#: served path reads the same, 0.125-0.145. The control - the same
#: reference with every matmul operand rounded to float8_e4m3fn (3
#: significant bits, the nearest precision below the one the
#: configuration states) - reads 3.66-4.66: 7.7-9.4 times the bound,
#: not correct. The bound lies between the two with room on both sides
#: (2.5 times the largest served reading, a ninth of the smallest
#: control). The state-only control (k, v and the summaries alone in
#: float8) reads 0.194-0.218 at 2,064 positions, inside the bound, and
#: 0.96-0.98 at 4,200, outside it: attention over a
#: thousand keys averages most of the state's rounding away, so it is
#: printed and does not decide. Every run prints all four on its
#: ``reference_detail`` line.
LOGIT_TOL = 0.4


def _kwargs(cfg):
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("rope_scaling") \
            or cfg.get("attention_bias") or cfg.get("tie_word_embeddings") \
            or cfg.get("attention_class") != "eva" \
            or not cfg.get("norm_add_unit_offset") \
            or not cfg.get("fp32_skip_add"):
        raise SystemExit("chipbench: archs/evabyte.py builds the published "
                         "block: eva attention, silu, unit-offset norms, a "
                         "float32 residual stream, no bias, no rope "
                         "scaling, an untied head")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise SystemExit("chipbench: archs/evabyte.py: every head has its "
                         "own keys and values in this configuration")
    return dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], pos_embed="rotary",
        rope_base=float(cfg["rope_theta"]), block="evabyte",
        window=cfg["window_size"], chunk=cfg["chunk_size"],
        n_pred_heads=cfg["num_pred_heads"],
        ffn_width=cfg["intermediate_size"], rms_eps=cfg["rms_norm_eps"],
        tie_head=False, embed_scale=False)


def decode_symbol(cfg, step_len):
    """The program's own builder call. Called before any weight is
    drawn, so a tree whose ``models/transformer.py`` lacks the block
    fails here, at once (TypeError: unexpected keyword ``window``)."""
    from mxnet_tpu.models import transformer as tfm
    return tfm.get_decode_symbol(
        capacity=cfg["capacity"], per_slot=True, step_len=step_len,
        max_seq_len=cfg["max_position_embeddings"], **_kwargs(cfg))


def data_shapes(cfg, slots, step_len):
    # rotary: no pos_ids; fed: the real tokens of each slot's step_len
    return {"data": (slots, step_len), "fed": (slots,)}


def make_params(symbol, data_shapes, seed, cfg):
    """Every parameter from the seed, in one jitted call, drawn in
    float32 and held at the dtype the configuration states (bfloat16:
    what a checkpoint of this model is, and what ``DecodeEngine`` then
    binds without a float32 master): N(0, 0.02) matrices and
    embeddings, ZERO norm gains (the norm scales by 1 + gain), and phi
    and mu N(0, 1) clipped to [-1, 1] times head_dim**-0.5, the
    published initialisation of ``adaptive_phi``/``adaptive_mu_k``.
    Parameter ``i`` of ``symbol.list_arguments()`` less the data inputs
    draws from ``fold_in(key, i)``."""
    names = symbol.list_arguments()
    shapes, _, _ = symbol.infer_shape(**data_shapes)
    todo = [(n, tuple(s)) for n, s in zip(names, shapes)
            if n not in data_shapes]
    dtype = jnp.dtype(cfg["param_dtype"])
    head = cfg["hidden_size"] // cfg["num_attention_heads"]

    def gen(key):
        out = {}
        for i, (name, shape) in enumerate(todo):
            draw = jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32)
            if name.endswith("_gamma"):
                out[name] = jnp.zeros(shape, dtype)
            elif name.endswith(("_phi", "_mu")):
                out[name] = (jnp.clip(draw, -1.0, 1.0)
                             * head ** -0.5).astype(dtype)
            else:
                out[name] = (0.02 * draw).astype(dtype)
        return out

    arrays = jax.jit(gen)(jax.random.PRNGKey(int(seed) % (1 << 31)))
    host = {}
    for name in list(arrays):
        arr = arrays.pop(name)
        host[name] = np.asarray(arr)
        arr.delete()
    return host


def _say(**fields):
    print(json.dumps({"chipbench": "reference_detail", **fields}),
          flush=True)


def _report(emu_err, state_err, state_over, ctrl_err, ctrl_over):
    _say(bfloat16_emulation_max_abs_err=float(emu_err),
         state_control="the reference with the attention state alone "
         "(rotated k, v and the summaries) rounded to float8_e4m3fn",
         state_control_max_abs_err=float(state_err),
         state_control_max_err_over_bound=float(state_over),
         control="the reference with every matmul operand rounded to "
         "float8_e4m3fn",
         control_max_abs_err=float(ctrl_err),
         control_max_err_over_bound=float(ctrl_over),
         control_correct=bool(ctrl_over <= 1.0), tolerance=LOGIT_TOL)
    return np.float32(0.0)


def reference_logits(params, tokens, cfg):
    """The plain reference's logits of head 0. Beside them, on a line
    of its own (``reference_detail``), what the comparison cannot say
    by itself: the reference's own bfloat16-operand emulation of the
    served path, and two controls against the same bound - the
    attention state alone in float8, and every matmul operand in float8
    (the one that has to come out not correct)."""
    want = _reference.forward(params, tokens, cfg)
    emu = _reference.forward(params, tokens, cfg, round_to=jnp.bfloat16)
    state = _reference.forward(params, tokens, cfg,
                               state_to=jnp.float8_e4m3fn)
    ctrl = _reference.forward(params, tokens, cfg,
                              round_to=jnp.float8_e4m3fn)
    bound = LOGIT_TOL + LOGIT_TOL * jnp.abs(want)
    state_err, ctrl_err = jnp.abs(state - want), jnp.abs(ctrl - want)
    # the line is printed before the logits are handed back: the
    # callback's result is part of them
    zero = jax.experimental.io_callback(
        _report, jax.ShapeDtypeStruct((), jnp.float32),
        jnp.max(jnp.abs(emu - want)), jnp.max(state_err),
        jnp.max(state_err / bound), jnp.max(ctrl_err),
        jnp.max(ctrl_err / bound), ordered=True)
    return want + zero


# ------------------------------------------------------------------ costs
def _width(cfg):
    return 2 if cfg["param_dtype"] == "bfloat16" else 4


def row_bytes(cfg):
    """K and V of one position (or of one summary), one layer, at the
    stated width: 16 KB at the published sizes."""
    return 2 * cfg["hidden_size"] * _width(cfg)


def keys_at(cfg, position):
    """``(exact rows, summaries)`` that a query at ``position`` attends:
    the open window's rows up to itself, and window_size / chunk_size
    summaries of every closed window."""
    W = cfg["window_size"]
    return (position % W + 1,
            (position // W) * (W // cfg["chunk_size"]))


def attention(cfg, slots, step_len, live_rows):
    """The EVA kernels of one dispatch, all layers: ``slots`` slots of
    ``step_len`` positions, each slot at context ``live_rows`` (mean
    over slots). FLOPs: scores and weighted sums over the keys a query
    attends (half the new rows on average), the pooling of the new rows.
    Bytes: the state read once a slot (exact rows and summaries), q, k,
    v in and the output out, the new rows and their summaries written."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    exact, pooled = keys_at(cfg, int(live_rows))
    tokens = slots * step_len
    keys = exact + pooled + step_len / 2.0
    return {"flops": L * (4.0 * tokens * keys * d + 6.0 * tokens * d),
            "bytes": L * (slots * (exact + pooled) * row_bytes(cfg)
                          + 4 * tokens * d * _width(cfg)
                          + tokens * row_bytes(cfg)
                          * (1.0 + 1.0 / cfg["chunk_size"]))}


def step(cfg, slots, step_len, live_rows):
    """One dispatch of the slot-pooled decode program. What the
    algorithm needs at the stated width: every weight once, the
    embedding rows, the live state (``attention``), float32 logits of
    head 0 out. Pads count as tokens."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], \
        cfg["vocab_size"]
    F, P = cfg["intermediate_size"], cfg["num_pred_heads"]
    tokens = slots * step_len
    layer = 4 * d * d + 3 * d * F + 2 * d         # + phi and mu
    att = attention(cfg, slots, step_len, live_rows)
    return {"flops": 2.0 * tokens * (L * (4 * d * d + 3 * d * F)
                                     + P * V * d) + att["flops"],
            "bytes": (L * layer + P * V * d) * _width(cfg)
            + tokens * d * _width(cfg)                    # embedding rows
            + att["bytes"]
            + tokens * V * 4}                             # logits out


def costs(cfg, slots, step_len, live_rows):
    return {"decode_step": step(cfg, slots, 1, live_rows),
            "window_step": step(cfg, slots, step_len, live_rows),
            "eva_window": attention(cfg, slots, step_len, live_rows),
            "eva_row": {"flops": 0.0, "bytes": row_bytes(cfg)}}
