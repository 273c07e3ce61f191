"""Architecture "granite_moe_hybrid": ``models/transformer.py``'s Granite
4.0-H decoder with routed experts (``block="granite_hybrid"`` with
``num_local_experts`` > 0 - Granite 4.0-H Small: per layer a Mamba-2
mixer whose state is constant in the context - ``ops/ssm.py`` - or
grouped attention without positions - ``rtc.py``'s ``attention_decode``
-, then 72 softmax-routed experts, 10 a token, of which this chip holds
a share, beside a shared gated-SiLU feed-forward - ``ops/moe.py`` -, the
four Granite multipliers, a tied head over a slice of the vocabulary),
served through ``serve_decoder``. The ``serve`` interface of
chipbench/README.md; the configuration's keys are the published
config.json's, with ``num_experts_held`` and ``held_first`` (the share)
and ``layers_run`` beside them. What Micro's architecture file
(archs/granite_hybrid.py) states of the mixers - how their parameters
are drawn, what a state and a row cost - is taken from it."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.archs import granite_hybrid as _micro
from chipbench.archs.granite_hybrid import (kv_row_bytes, ssm_row,
                                            ssm_state_bytes)
from chipbench.archs.xing4 import TailLogits
from chipbench.reference import granite_moe_hybrid as _reference

#: |served - reference| <= TOL + TOL * |reference| on every compared
#: logit. The served path holds parameters and the stream in bfloat16
#: and multiplies in bfloat16 with float32 accumulation through 10
#: layers of width 4,096; the Mamba-2 state, the convolution's tail, the
#: decay products, every accumulation of the scan, the router's softmax
#: and the experts' weighted sum are float32. The reference upcasts the
#: same parameters and computes in float32 at the highest matmul
#: precision, the recurrence step by step, the router as published
#: (top-k of the logits, softmax over those), one expert at a time. The
#: logits are small (a tied head of N(0, 0.02) rows over a unit-RMS
#: stream of width 4,096, divided by 16: |logit| up to 1.12), so the
#: bound is nearly absolute. One thing is discontinuous: a rounding can
#: move the tenth and the eleventh largest of 72 router logits past
#: each other, and the token then passes another expert - at the
#: smallest of its ten weights, since the softmax is over the chosen
#: logits, so a flip moves the output by one small gate and not by a
#: tenth of the layer (``choice_flip_share`` 0.066-0.074 between the
#: reference and its own bfloat16 emulation, and the emulation no
#: further off than the served path).
#: The readings (my chip runs, PR 54; PERF.md, section 6; positions
#: 1,008-1,039 of two sequences, each the largest ``err / (1 +
#: |reference|)`` over the compared logits, linear in the bound;
#: thirteen seeds): the served path **0.0084-0.0131** (``max_abs_err``
#: 0.0088-0.0132; whole-window program then S = 1; the packed window
#: program at 3,840 positions through tools/window_pack_check.py 0.0058,
#: the whole-window program beside it 0.0091); the reference's own
#: bfloat16-operand emulation 0.0071-0.0142 - the served path is the
#: emulation's size. The control that has to come out not correct, every
#: matmul operand rounded to float8_e4m3fn (the nearest precision below
#: the one stated): **0.092-0.111**, not correct on every seed. The
#: bound lies between the two with 1.7 of room below and 4.2 above; it
#: is set where it is, and not at their middle, for the two controls
#: that break the routed layer: the experts left out of every layer
#: (the shared feed-forward alone) read **0.088-0.122**, and the gates
#: not renormalised over the chosen (the softmax over all 72 at the ten
#: chosen: about half the weight) **0.039-0.048**, 1.8 of the bound at
#: the least - not correct on every seed, so the comparison sees both
#: the experts and the published order of top-k and softmax. Two
#: controls break the Mamba-2 state: a state that carries nothing from
#: token to token reads **0.29-0.37** and one dropped at every multiple
#: of ``prefill_chunk`` (the hand-over between two windows lost)
#: **0.22-0.27**, ten times the bound. What the comparison cannot see
#: is the state's WIDTH: rounded to bfloat16 after every token it reads
#: 0.006-0.013 (a third to a half of the bound), the served path's own
#: size (PR 48 and
#: PR 52 found the same; the CPU's float32 comparison does see it:
#: tests/test_granite_hybrid.py). Every run prints the emulation and
#: the six controls on its ``reference_detail`` line. (The first run
#: ran under a placeholder of 0.06, under which the raw gates read
#: correct at 0.75 of it; the bound was set from that run's readings
#: and the twelve runs after it ran under 0.022, the largest at 0.60.)
LOGIT_TOL = 0.022

#: positions at the end of the sequences over which the controls and
#: the emulation are compared: serve_runner.check_reference's last 16
#: of the window path and 16 of the S=1 path
_TAIL = 32


def _granite(cfg):
    """``get_decode_symbol(granite=...)``: the published keys, the
    layers cut to those that are run, and the share."""
    from mxnet_tpu.models import transformer as tfm
    keys = getattr(tfm, "GRANITE_KEYS", ())
    if "num_experts_per_tok" not in keys:
        raise SystemExit("chipbench: this tree's models/transformer.py "
                         "builds no block 'granite_hybrid' with routed "
                         "experts")
    given = {k: cfg[k] for k in keys}
    return dict(given, layer_types=_reference.layer_types(cfg),
                held=(cfg["held_first"], cfg["num_experts_held"]))


def decode_symbol(cfg, step_len):
    """The program's own builder call. Called before any weight is
    drawn, so a tree whose ``models/transformer.py`` lacks the routed
    layer fails here, at once."""
    from mxnet_tpu.models import transformer as tfm
    granite = _granite(cfg)
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("attention_bias") \
            or not cfg.get("tie_word_embeddings") \
            or cfg.get("normalization_function", "rmsnorm") != "rmsnorm" \
            or not cfg["num_local_experts"] \
            or len(cfg["layers_run"]) != cfg["num_hidden_layers"]:
        raise SystemExit("chipbench: archs/granite_moe_hybrid.py builds the "
                         "published block: silu, RMSNorm, no attention "
                         "bias, a tied head, routed experts beside the "
                         "shared feed-forward, one entry of layers_run a "
                         "layer that is run")
    return tfm.get_decode_symbol(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], capacity=cfg["capacity"],
        per_slot=True, step_len=step_len, block="granite_hybrid",
        rms_eps=cfg["rms_norm_eps"], granite=granite)


def data_shapes(cfg, slots, step_len):
    # no positions: no pos_ids; fed: the real tokens of each slot
    return {"data": (slots, step_len), "fed": (slots,)}


#: the parameters that archs/granite_hybrid.py's ``_draw`` tells from a
#: matrix by the end of their name
_KINDS = ("_gamma", "_mamba_D", "_mamba_A_log", "_mamba_dt_bias",
          "_mamba_conv_weight", "_mamba_conv_bias")


@functools.lru_cache(maxsize=None)
def _drawer(kind, shape, dtype, taps):
    """One parameter of ``kind`` in float32, held at ``dtype``, from a
    key (``make_params``)."""
    return jax.jit(lambda key: _micro._draw(kind, shape, key, taps)
                   .astype(dtype))


def make_params(symbol, data_shapes, seed, cfg):
    """Every parameter from the seed, drawn in float32 and held at the
    dtype the configuration states (bfloat16), one jitted call a
    parameter (9.5 GB: a parameter is on the host before the next is
    drawn). As archs/granite_hybrid.py draws them: matrices - the
    router, the experts and the shared feed-forward among them - and
    the embedding N(0, 0.02), norm gains 1, the mixer's own as Mamba-2
    initialises them (``A_log``, ``dt_bias``, ``D``, the convolution:
    a token's decay between 0.2 and 0.999 a head). A normed row through
    the router is then 72 logits of deviation 1.28: the ten chosen
    carry weights of a few hundredths to a third, no expert is
    favoured, and the load is even but for chance. Parameter ``i`` of
    ``symbol.list_arguments()`` less the data inputs draws from
    ``fold_in(key, i)``."""
    names = symbol.list_arguments()
    shapes, _, _ = symbol.infer_shape(**data_shapes)
    todo = [(n, tuple(s)) for n, s in zip(names, shapes)
            if n not in data_shapes]
    dtype = jnp.dtype(cfg["param_dtype"])
    key = jax.random.PRNGKey(int(seed) % (1 << 31))
    host = {}
    for i, (name, shape) in enumerate(todo):
        kind = next((k for k in _KINDS if name.endswith(k)), "")
        arr = _drawer(kind, shape, dtype, cfg["mamba_d_conv"])(
            jax.random.fold_in(key, i))
        host[name] = np.asarray(arr)
        arr.delete()
    return host


def _controls(cfg):
    """The controls of the ``reference_detail`` line: (key, what it is,
    ``forward``'s switches). One lowers the stated precision of the
    matmuls, two break the routed layer, two the mixers' state, and one
    lowers the state's precision (which the comparison cannot see)."""
    return (
        ("fp8", "the reference with every matmul operand rounded to "
         "float8_e4m3fn", {"round_to": jnp.float8_e4m3fn}),
        ("experts_out", "the same reference with the routed experts left "
         "out of every layer (the shared feed-forward alone)",
         {"routed": False}),
        ("gates_raw", "the same reference with the chosen experts weighed "
         "by the softmax over all 72, not renormalised over the chosen",
         {"renorm": False}),
        ("state_none", "the same reference with a state that carries "
         "nothing from one token to the next", {"state_every": 1}),
        ("state_lost", "the same reference with the state dropped at "
         "every multiple of prefill_chunk (the hand-over between two "
         "windows lost)", {"state_every": cfg["prefill_chunk"]}),
        ("state_bf16", "the same reference with the recurrent state "
         "rounded to bfloat16 after every token",
         {"state_dtype": jnp.bfloat16}))


def _report(controls, flip, emu_err, emu_over, *readings):
    fields = {}
    for i, (key, what, _switches) in enumerate(controls):
        err, over = readings[2 * i], readings[2 * i + 1]
        fields[f"{key}_control"] = what
        fields[f"{key}_control_max_abs_err"] = float(err)
        fields[f"{key}_control_max_err_over_bound"] = float(over)
        fields[f"{key}_control_correct"] = bool(over <= 1.0)
    print(json.dumps({
        "chipbench": "reference_detail", "positions_compared": _TAIL,
        "choice_flip_share": float(flip),
        "choices_compared": "float32 reference against its own bfloat16-"
        "operand emulation of the served path: share of (layer, token) "
        "routing decisions with another set of experts",
        "bfloat16_emulation_max_abs_err": float(emu_err),
        "bfloat16_emulation_max_err_over_bound": float(emu_over), **fields,
        "tolerance": LOGIT_TOL}), flush=True)
    return np.float32(0.0)


def reference_logits(params, tokens, cfg):
    """The plain reference's logits at the positions ``check_reference``
    compares - the last ``_TAIL`` -, as ``TailLogits``. Beside them, on
    a line of its own (``reference_detail``), over the same positions:
    the reference's own bfloat16-operand emulation of the served path
    with the share of routing decisions it moves, and the controls
    against the same bound. One forward after another (each waits for
    the last: all at once do not fit beside a live engine)."""
    T = tokens.shape[1]
    tail = min(_TAIL, T)
    ref, chosen = _reference.forward(params, tokens, cfg, tail=tail,
                                     return_chosen=True)

    def after(x):
        return jax.lax.optimization_barrier((tokens, x))[0]

    emu, emu_chosen = _reference.forward(
        params, after(ref), cfg, round_to=jnp.bfloat16, tail=tail,
        return_chosen=True)
    flip = _reference.choice_flip_share(chosen, emu_chosen)
    bound = LOGIT_TOL + LOGIT_TOL * jnp.abs(ref)
    controls = _controls(cfg)
    readings, last = [], emu
    for _key, _what, switches in controls:
        last = _reference.forward(params, after(last), cfg, tail=tail,
                                  **switches)
        err = jnp.abs(last - ref)
        readings += [jnp.max(err), jnp.max(err / bound)]
    emu_err = jnp.abs(emu - ref)
    zero = jax.experimental.io_callback(
        functools.partial(_report, controls),
        jax.ShapeDtypeStruct((), jnp.float32), flip,
        jnp.max(emu_err), jnp.max(emu_err / bound), *readings, ordered=True)
    return TailLogits(ref + zero, T)


# ------------------------------------------------------------------ costs
def _run(cfg):
    """The configuration as the mixers' cost functions of
    archs/granite_hybrid.py read it: ``layer_types`` of the layers that
    are run."""
    return dict(cfg, layer_types=_reference.layer_types(cfg))


def moe_expert_bytes(cfg):
    """One routed expert's three matrices at the stated width
    (18,874,368 B)."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"] \
        * _micro._width(cfg)


def moe_assignment(cfg):
    """One (token, expert) assignment through a routed expert: its
    three products - 18.87 MFLOP - and the row in and out at the stated
    width."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    return {"flops": 6.0 * D * F, "bytes": 2 * D * _micro._width(cfg)}


def held_touched(cfg, tokens):
    """Expected held experts with at least one of ``tokens`` tokens'
    assignments under even routing (a token's choice falls on a given
    expert with k / E)."""
    E, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    return cfg["num_experts_held"] * (1.0 - (1.0 - k / E) ** tokens)


def step(cfg, slots, step_len, live_rows):
    """One dispatch of the slot-pooled decode program. What the
    algorithm needs at the stated width: every weight outside the
    routed experts once (the mixers, the shared feed-forward, the
    router, the embedding as the tied head), the held experts touched
    (even-routing expectation) once, the embedding rows, every fed
    slot's recurrent state read and written in every mamba layer
    (``ssm_state_bytes``) and a row's operands through it
    (``ssm_row``), the live K/V rows of the attention layer and the new
    rows written, float32 logits over the held vocabulary out. Pads
    count as tokens."""
    run = _run(cfg)
    D, V, w = cfg["hidden_size"], cfg["vocab_size"], _micro._width(cfg)
    Fs, F = cfg["shared_intermediate_size"], cfg["intermediate_size"]
    E, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = D // heads
    H, _P, _N, d_in, C = _micro._mixer(cfg)
    n_mamba, n_attn = _micro._layers(run)
    layers = n_mamba + n_attn
    mamba = D * (d_in + C + H) + d_in * D + C * (cfg["mamba_d_conv"] + 1) \
        + 3 * H + d_in
    attn = D * (heads + 2 * kv) * dh + heads * dh * D
    outside = n_mamba * mamba + n_attn * attn \
        + layers * (3 * D * Fs + E * D + 2 * D) + V * D + D
    tokens = slots * step_len
    here = k * cfg["num_experts_held"] / E
    touched = held_touched(cfg, tokens)
    row = ssm_row(cfg)
    keys = live_rows + step_len / 2.0
    return {"flops": 2.0 * tokens * (outside + layers * here * 3 * D * F)
            + tokens * n_mamba * row["flops"]
            + tokens * n_attn * keys * 4.0 * heads * dh,
            "bytes": outside * w + layers * touched * moe_expert_bytes(cfg)
            + tokens * D * w + slots * n_mamba * ssm_state_bytes(cfg)
            + tokens * n_mamba * row["bytes"]
            + n_attn * (slots * (live_rows + step_len) + tokens)
            * kv_row_bytes(cfg) + tokens * V * 4,
            "weights_outside_experts": outside,
            "held_experts_touched_per_layer": touched}


def costs(cfg, slots, step_len, live_rows):
    return {"decode_step": step(cfg, slots, 1, live_rows),
            "window_step": step(cfg, slots, step_len, live_rows),
            "ssm_state": {"flops": 0.0, "bytes": ssm_state_bytes(cfg)},
            "ssm_row": ssm_row(cfg),
            "moe_expert": {"flops": 0.0, "bytes": moe_expert_bytes(cfg)},
            "moe_assignment": moe_assignment(cfg)}
