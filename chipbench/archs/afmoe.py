"""Architecture "afmoe": ``models/transformer.py``'s Trinity decoder
(``block="afmoe"``: grouped K/V heads, sliding-window layers whose
pools are rings beside full layers without positions - ``rtc.py``'s
``attention_decode`` -, q and k normed per head, a gated attention
output, four norms a layer, a leading dense layer, then sigmoid-routed
experts, all held, beside a shared one - ``ops/moe.py`` -, a scaled
embedding, an untied head over a slice of the vocabulary), served
through ``serve_decoder``. The ``serve`` interface of
chipbench/README.md; the configuration's keys are the published
config.json's, with ``layer_types_run`` (the layers that are run)
beside them."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import afmoe as _reference

#: |served - reference| <= TOL + TOL * |reference| on every compared
#: logit. The served path holds parameters, pools and rings in bfloat16
#: and multiplies in bfloat16 with float32 accumulation through 5
#: layers; the reference upcasts the same parameters and computes in
#: float32 at the highest matmul precision. The router is
#: discontinuous, and here it decides the reading: with N(0, 0.02)
#: weights the 8th and 9th of 128 sigmoid scores lie within bfloat16's
#: rounding of each other for one (layer, sequence, position) decision
#: in eleven (``routing_flip_share`` 8.9-9.9 %, against OLMoE's 3-4 %),
#: and a flipped expert carries route_scale / 8 = 0.35 of a layer's
#: routed sum, where a softmax router's carries a few hundredths. So
#: the bound is set from readings and not from the step size. Measured
#: on the v5e at the published widths (my chip runs, PR 38; PERF.md,
#: Findings), positions 4,080-4,111 of two sequences, 14 seeds, every
#: reading as max |err| / (1 + |reference|), the TOL that would just
#: pass it, on logits of magnitude up to 4.4-5.0: the served path
#: 0.82-1.36 (max |err| 0.92-1.49; twelve of fourteen seeds under
#: 1.09), the reference's own bfloat16-operand emulation max |err|
#: 0.85-1.50 - the same; the control, every matmul operand rounded to
#: float8_e4m3fn (the nearest precision below the one stated),
#: 2.35-2.87: not correct, 1.24-1.51 times this bound; the sliding
#: layers attending every j <= t (no window: at these positions twice a
#: sliding layer's keys) 3.06-3.58: not correct, 1.61-1.88 times. The
#: bound lies between 1.36 and 2.35, nearer the controls, because the
#: served side is a maximum over flipped decisions and has the longer
#: tail. **The third control, rotary positions on the full layer too,
#: reads 0.45-0.63 - under the served path itself, so this comparison
#: cannot tell it**: with these weights the full layer's attention is
#: close to a mean over 4,100 keys behind a norm, and moving every
#: key's phase moves the logits by less than the routing's own noise.
#: It is told on the CPU in float32 at a small size
#: (tests/test_afmoe.py: 100 times that bound); PERF.md section 7 says
#: what a comparison that could tell it on the chip would need. Every
#: run prints the emulation and the three controls on its
#: ``reference_detail`` line.
LOGIT_TOL = 1.9

#: positions at the end of the sequences over which the controls and
#: the emulation are compared: serve_runner.check_reference's last 16
#: of the window path and 16 of the S=1 path
_TAIL = 32


def _afmoe(cfg):
    from mxnet_tpu.models import transformer as tfm
    spec = {k: cfg[k] for k in tfm.AFMOE_KEYS if k != "layer_types"}
    spec["layer_types"] = cfg["layer_types_run"]
    return spec


def _reference_cfg(cfg):
    return dict(cfg, layer_types=cfg["layer_types_run"])


def decode_symbol(cfg, step_len):
    """The program's own builder call. Called before any weight is
    drawn, so a tree whose ``models/transformer.py`` lacks the block
    fails here, at once (no ``AFMOE_KEYS``)."""
    from mxnet_tpu.models import transformer as tfm
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("rope_scaling") \
            or cfg.get("tie_word_embeddings") or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["score_func"] != "sigmoid" \
            or not cfg["mup_enabled"] \
            or len(cfg["layer_types_run"]) != cfg["num_hidden_layers"]:
        raise SystemExit("chipbench: archs/afmoe.py builds the published "
                         "block: silu, no rope scaling, an untied head, a "
                         "sigmoid router without group limit, a scaled "
                         "embedding, one layer type a layer that is run")
    return tfm.get_decode_symbol(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], pos_embed="rotary",
        rope_base=float(cfg["rope_theta"]), capacity=cfg["capacity"],
        per_slot=True, step_len=step_len, block="afmoe",
        rms_eps=cfg["rms_norm_eps"], tie_head=False, embed_scale=True,
        afmoe=_afmoe(cfg), max_step_len=cfg["prefill_chunk"])


def data_shapes(cfg, slots, step_len):
    # rotary or none: no pos_ids; fed: the real tokens of each slot
    return {"data": (slots, step_len), "fed": (slots,)}


def make_params(symbol, data_shapes, seed, cfg):
    """Every parameter from the seed, in one jitted call, drawn in
    float32 and held at the dtype the configuration states (bfloat16):
    N(0, 0.02) matrices, embeddings and the router's ``expert_bias``,
    unit norm gains. Parameter ``i`` of ``symbol.list_arguments()``
    less the data inputs draws from ``fold_in(key, i)``."""
    names = symbol.list_arguments()
    shapes, _, _ = symbol.infer_shape(**data_shapes)
    todo = [(n, tuple(s)) for n, s in zip(names, shapes)
            if n not in data_shapes]
    dtype = jnp.dtype(cfg["param_dtype"])

    def gen(key):
        out = {}
        for i, (name, shape) in enumerate(todo):
            if name.endswith("_gamma"):
                out[name] = jnp.ones(shape, dtype)
            else:
                out[name] = (0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                ).astype(dtype)
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


def _report(flip, emu, fp8, fp8_over, dense, dense_over, rope, rope_over):
    _say(positions_compared=_TAIL,
         routing_flip_share=float(flip),
         routing_compared="float32 reference against its own bfloat16-"
         "operand emulation of the served path, share of (layer, "
         "sequence, position) decisions with another expert set",
         bfloat16_emulation_max_abs_err=float(emu),
         control="the reference with every matmul operand rounded to "
         "float8_e4m3fn",
         control_max_abs_err=float(fp8),
         control_max_err_over_bound=float(fp8_over),
         control_correct=bool(fp8_over <= 1.0),
         window_control="the same reference with the sliding layers "
         "attending every j <= t (no window)",
         window_control_max_abs_err=float(dense),
         window_control_max_err_over_bound=float(dense_over),
         window_control_correct=bool(dense_over <= 1.0),
         rope_control="the same reference with rotary positions on the "
         "full layers too",
         rope_control_max_abs_err=float(rope),
         rope_control_max_err_over_bound=float(rope_over),
         rope_control_correct=bool(rope_over <= 1.0),
         tolerance=LOGIT_TOL)
    return np.float32(0.0)


def reference_logits(params, tokens, cfg):
    """The plain reference's logits. Beside them, on a line of its own
    (``reference_detail``), over the last ``_TAIL`` positions - the
    ones ``check_reference`` compares -: the reference's own bfloat16-
    operand emulation of the served path with the share of routing
    decisions it moves, and the three controls against the same bound.
    One forward after another (each waits for the last: five at once do
    not fit beside a live engine)."""
    rcfg = _reference_cfg(cfg)
    tail = min(_TAIL, tokens.shape[1])
    want, chosen = _reference.forward(params, tokens, rcfg,
                                      return_routing=True)
    ref = want[:, -tail:]

    def after(x):
        return jax.lax.optimization_barrier((tokens, x))[0]

    emu, emu_chosen = _reference.forward(
        params, after(ref), rcfg, round_to=jnp.bfloat16, tail=tail,
        return_routing=True)
    fp8 = _reference.forward(params, after(emu), rcfg,
                             round_to=jnp.float8_e4m3fn, tail=tail)
    dense = _reference.forward(params, after(fp8), rcfg, window=False,
                               tail=tail)
    rope = _reference.forward(params, after(dense), rcfg, rope_full=True,
                              tail=tail)
    bound = LOGIT_TOL + LOGIT_TOL * jnp.abs(ref)
    errs = [jnp.abs(x - ref) for x in (fp8, dense, rope)]
    # the line is printed before the logits are handed back: the
    # callback's result is part of them
    zero = jax.experimental.io_callback(
        _report, jax.ShapeDtypeStruct((), jnp.float32),
        _reference.routing_flip_share(chosen, emu_chosen),
        jnp.max(jnp.abs(emu - ref)),
        *[f(e) for e in errs for f in (jnp.max,
                                       lambda e: jnp.max(e / bound))],
        ordered=True)
    return want + zero


# ------------------------------------------------------------------ costs
def _width(cfg):
    return 2 if cfg["param_dtype"] == "bfloat16" else 4


def kv_row_bytes(cfg):
    """One position's K and V, one layer (2,048 B)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * _width(cfg)


def moe_expert_bytes(cfg):
    """One routed expert's three matrices at the stated width."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * _width(cfg)


def experts_touched(cfg, tokens):
    """Expected experts with at least one of ``tokens`` tokens'
    assignments under EVEN routing; the measured count is the per-layer
    metric ``moe.experts_touched_per_layer_step``."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** tokens)


def _layers(cfg):
    kinds = cfg["layer_types_run"]
    sliding = sum(k == "sliding_attention" for k in kinds)
    return len(kinds), sliding, len(kinds) - cfg["num_dense_layers"]


def attention(cfg, slots, step_len, live_rows):
    """What the attention reads of one dispatch have to do, all
    layers: ``slots`` slots of ``step_len`` queries, each slot at
    context ``live_rows``, the ATTENDED keys alone. A query of a full
    layer attends every position at or before it, one of a sliding
    layer at most ``sliding_window`` of them. FLOPs: scores and
    weighted sums of every query head. Bytes: the K and V rows that
    some query of the slot attends, once a slot (a group's 8 query
    heads share their K/V head's rows); q in and the output out; the
    new rows written."""
    L, sliding, _ = _layers(cfg)
    H, dh, w = cfg["num_attention_heads"], cfg["head_dim"], _width(cfg)
    W = cfg["sliding_window"]
    tokens = slots * step_len
    keys_full = live_rows + step_len / 2.0
    keys_slide = min(keys_full, W)
    rows_full = live_rows + step_len
    rows_slide = min(rows_full, W + step_len)
    keys = (L - sliding) * keys_full + sliding * keys_slide
    rows = (L - sliding) * rows_full + sliding * rows_slide
    return {"flops": tokens * keys * 4.0 * H * dh,
            "bytes": slots * rows * kv_row_bytes(cfg)
            + L * tokens * 2 * H * dh * w
            + L * tokens * kv_row_bytes(cfg)}


def step(cfg, slots, step_len, live_rows):
    """One dispatch of the slot-pooled decode program. What the
    algorithm needs at the stated width: every weight outside the
    routed experts once, the experts touched (even-routing expectation)
    once, the embedding rows, the state (``attention``), float32 logits
    over the held vocabulary out. Pads count as tokens."""
    D, V, w = cfg["hidden_size"], cfg["vocab_size"], _width(cfg)
    L, _sliding, sparse = _layers(cfg)
    H, Hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    Fm, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    attn = D * 2 * (H + Hkv) * dh + H * dh * D
    dense_ffn = 3 * D * cfg["intermediate_size"]
    shared = 3 * D * Fm * cfg["num_shared_experts"]
    router = D * cfg["num_experts"]
    outside = L * attn + (L - sparse) * dense_ffn \
        + sparse * (shared + router) + V * D
    tokens = slots * step_len
    touched = experts_touched(cfg, tokens)
    att = attention(cfg, slots, step_len, live_rows)
    return {"flops": 2.0 * tokens * (outside + sparse * k * 3 * D * Fm)
            + att["flops"],
            "bytes": outside * w + sparse * touched * moe_expert_bytes(cfg)
            + tokens * D * w + att["bytes"] + tokens * V * 4,
            "experts_touched_per_layer": touched}


def costs(cfg, slots, step_len, live_rows):
    return {"decode_step": step(cfg, slots, 1, live_rows),
            "window_step": step(cfg, slots, step_len, live_rows),
            "attn_window": attention(cfg, slots, step_len, live_rows),
            "gqa_row": {"flops": 0.0, "bytes": kv_row_bytes(cfg)},
            "moe_expert": {"flops": 0.0, "bytes": moe_expert_bytes(cfg)}}
