"""Architecture "nemotron_h": ``models/transformer.py``'s Nemotron-H
decoder (``block="nemotron_h"`` - NVIDIA-Nemotron-3-Nano-30B-A3B: every
layer ONE sub-layer by its letter of ``hybrid_override_pattern``: ``M``
a Mamba-2 mixer with B and C in eight groups and a group-wise gated
norm, its state constant in the context - ``ops/ssm.py`` -; ``*``
grouped attention without positions, 16 query heads a K/V head -
``rtc.py``'s ``attention_decode`` -; ``E`` 128 sigmoid-routed ungated
experts ``down(relu(up x)^2)``, 6 a token, of which this chip holds a
share, beside a shared expert of the same form - ``ops/moe.py`` -; an
unscaled embedding and an untied head over a slice of the vocabulary),
served through ``serve_decoder``. The ``serve`` interface of
chipbench/README.md; the configuration's keys are the published
config.json's, with ``n_routed_experts_held`` and ``held_first`` (the
share) and ``layers_run`` beside them."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.archs import granite_hybrid as _micro
from chipbench.archs.xing4 import TailLogits
from chipbench.reference import nemotron_h as _reference

#: |served - reference| <= TOL + TOL * |reference| on every compared
#: logit. The served path holds parameters and the stream in bfloat16
#: and multiplies in bfloat16 with float32 accumulation through 16
#: sub-layers of width 2,688; the Mamba-2 state, the convolution's tail,
#: the decay products, every accumulation of the scan, the gated norm's
#: statistic, the router's sigmoid and the experts' weighted sum are
#: float32. The reference upcasts the same parameters and computes in
#: float32 at the highest matmul precision, the recurrence step by step
#: with B and C by group, the router as published, one expert at a time.
#: The logits are of order one (an untied head of N(0, 0.02) rows over a
#: normed stream of width 2,688: deviation 1.04, |logit| up to 5.6), so
#: the bound is part absolute, part relative. One thing is
#: discontinuous: a rounding can move the sixth and the seventh largest
#: of 128 choice scores past each other, and the token then passes
#: another expert at 0.42 of the layer's routed weight
#: (``choice_flip_share`` 0.021-0.027 between the reference and its own
#: bfloat16 emulation: nine to twelve of the 448 decisions compared).
#: What a flip moves, and what the mixers' state carries into the
#: logits, is set by ``make_params``' draw - the fourth of this PR: under
#: the first two every reading was the flips', under the third the
#: three controls of the mixer read the served path's own size
#: (``make_params``; PERF.md section 6 has every draw's readings).
#: The readings under this draw (my chip runs, PR 63, calls G, H, I and
#: K: eleven seeds; positions 1,008-1,039 of two sequences, each the
#: largest ``err / (1 + |reference|)``, which is linear in the bound;
#: the seeds and every number in PERF.md section 6): the served path
#: **0.073-0.112** (``max_abs_err`` 0.084-0.134; whole-window program
#: then S = 1; the two fed windows - a chunk's last row and a rider's -
#: 0.030-0.066), the reference's own bfloat16-operand emulation
#: 0.044-0.092: the served path reads up to half as much again as the
#: emulation, which rounds the operands of the dense products and not
#: those of the chunked scan. The control that has to come out not
#: correct, every matmul operand rounded to float8_e4m3fn (the nearest
#: precision below the one stated): **0.447-0.569**, not correct on
#: every seed. The bound lies between the two, 1.8 times the largest
#: served reading and 0.45 of the smallest fp8 one (their geometric
#: middle is 0.22; it stands a little below so that the routed layer's
#: controls keep their side of it). The other controls, on the four
#: seeds of calls G and K (a one-off wrapper: a committed run prints
#: ``_PER_RUN`` alone): those that break the mixer - every head reading
#: group 0's B and C **2.75-3.40**, the norm's statistic over all 4,096
#: channels **1.85-2.07**, a state that carries nothing **3.16-3.37** -
#: read nine to seventeen bounds on every seed; those that break the
#: routed layer - the experts left out **0.275-0.396**, relu for relu
#: squared **0.339-0.551** - not correct on every seed; the gates
#: without ``routed_scaling_factor`` (weights 1 where 2.5) 0.168-0.238,
#: AT the bound and not to be counted on. **What the comparison cannot
#: see**: the correction bias left out of the choice, 0.119, 0.123 and
#: 0.144 on three seeds and 0.287 on one (7 % of the assignments go
#: elsewhere, each a flip: the served path's own size under any draw -
#: what it moves is what a rounding moves). The CPU's float32
#: comparison sees every control by a hundred bounds
#: (tests/test_nemotron_h.py). Every run prints the emulation and the
#: fp8 control on its ``reference_detail`` line. (Call G ran under 0.15,
#: the third draw's limit; the others under 0.2, set from call G's
#: readings before them.)
LOGIT_TOL = 0.2

#: positions at the end of the sequences over which the controls and
#: the emulation are compared: serve_runner.check_reference's last 16
#: of the window path and 16 of the S=1 path
_TAIL = 32


def _published(cfg):
    """``get_decode_symbol(nemotron_h=...)``: the published keys, the
    pattern cut to the layers that are run, and the share."""
    from mxnet_tpu.models import transformer as tfm
    keys = getattr(tfm, "NEMOTRON_H_KEYS", None)
    if keys is None:
        raise SystemExit("chipbench: this tree's models/transformer.py "
                         "builds no block 'nemotron_h'")
    given = {k: cfg[k] for k in keys}
    return dict(given,
                hybrid_override_pattern="".join(_reference.layer_kinds(cfg)),
                held=(cfg["held_first"], cfg["n_routed_experts_held"]))


def decode_symbol(cfg, step_len):
    """The program's own builder call. Called before any weight is
    drawn, so a tree whose ``models/transformer.py`` lacks the block
    fails here, at once."""
    from mxnet_tpu.models import transformer as tfm
    published = _published(cfg)
    if cfg.get("tie_word_embeddings") or cfg.get("residual_in_fp32") \
            or cfg.get("sliding_window") is not None \
            or cfg.get("use_bias") \
            or len(cfg["layers_run"]) != cfg["num_hidden_layers"]:
        raise SystemExit("chipbench: archs/nemotron_h.py builds the "
                         "published block: an untied head, the stream at "
                         "the compute width, no sliding window, no bias, "
                         "one entry of layers_run a layer that is run")
    return tfm.get_decode_symbol(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], capacity=cfg["capacity"],
        per_slot=True, step_len=step_len, block="nemotron_h",
        rms_eps=cfg["layer_norm_epsilon"], nemotron_h=published)


def data_shapes(cfg, slots, step_len):
    # no positions: no pos_ids; fed: the real tokens of each slot
    return {"data": (slots, step_len), "fed": (slots,)}


#: the parameters that are not drawn N(0, 0.02), by the end of their
#: name: gains and the mixer's own (archs/granite_hybrid.py's ``_draw``
#: tells them apart; ``D`` is not among them: ``make_params``), the
#: router's correction bias, the embedding, and the experts' last matrix
_KINDS = ("_gamma", "_mamba_A_log", "_mamba_dt_bias", "_mamba_conv_weight",
          "_mamba_conv_bias", "_moe_router_bias", "_tok_embed_weight",
          "_moe_down_weight", "_moe_shared_down_weight")
#: the deviation of the router's correction bias (``make_params``)
_BIAS_DEVIATION = 0.01
_DEVIATION = 0.02
#: the RMS of the embedding's rows (``make_params``: the stream's size
#: beside a sub-layer's output, set from readings)
_STREAM_RMS = 4.0


def _draw(kind, shape, key, taps, depth):
    normal = lambda dev: dev * jax.random.normal(          # noqa: E731
        key, shape, jnp.float32)
    if kind == "_moe_router_bias":
        return normal(_BIAS_DEVIATION)
    if kind == "_tok_embed_weight":
        return normal(_STREAM_RMS)
    if kind in ("_moe_down_weight", "_moe_shared_down_weight"):
        return normal(_DEVIATION / np.sqrt(2.0 * depth))
    return _micro._draw(kind, shape, key, taps)


@functools.lru_cache(maxsize=None)
def _drawer(kind, shape, dtype, taps, depth):
    """One parameter of ``kind`` in float32, held at ``dtype``, from a
    key (``make_params``)."""
    return jax.jit(lambda key: _draw(kind, shape, key, taps, depth)
                   .astype(dtype))


def make_params(symbol, data_shapes, seed, cfg):
    """Every parameter from the seed, drawn in float32 and held at the
    dtype the configuration states (bfloat16), one jitted call a
    parameter (10.6 GB: a parameter is on the host before the next is
    drawn). Matrices - the router, the experts' ``up``, the shared
    expert's, the mixers' and the attention's projections in AND out -
    and the head N(0, 0.02), norm gains 1, the mixer's ``A_log``,
    ``dt_bias`` and convolution as archs/granite_hybrid.py draws them
    (``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of
    ``exp(U(log 1e-3, log 1e-1))``, the convolution ``U(-1/2, 1/2)``: a
    token's decay between 0.2 and 0.999 a head). Three things are set so
    that the comparison that decides ``correct`` sees each mechanism
    the cell exists for (readings: PERF.md section 6):

    * **the embedding's rows are N(0, ``_STREAM_RMS``^2) = N(0, 16)**: a
      residual path of RMS 4 under the sub-layers' outputs. With N(0,
      0.02) rows the stream is sub-layer outputs alone from the first
      layer on and seven relu-squared layers double a rounding seven
      times (the float32 reference's own bfloat16 emulation read 1.99
      from it on logits of 5.6: a comparison that sees nothing).
    * **the experts' last matrix** - every routed expert's and the
      shared expert's ``down`` - **is N(0, 0.02 / sqrt(2 x 52))**,
      Megatron-LM's scaled initialisation at the published depth: an
      expert layer adds 0.19 of RMS, of which one expert chosen
      otherwise by a rounding - 0.42 of a layer's routed weight, and
      not continuous - is 0.015 (with rows of unit RMS every perturbed
      forward read what its flipped experts gave it).
    * **the mixers speak through their state.** ``D`` is N(0, 0.02)
      like a matrix, not Mamba-2's initial 1, and the mixers' ``W_out``
      keeps N(0, 0.02): a mixer adds 1.28 of RMS, seven of them 3.4 to
      the embedding's 4. One mixer at the published widths (the CPU,
      two seeds, 600 tokens) with every head on group 0's B and C,
      with one statistic over 4,096, or without a state gives an
      output that differs from the whole mixer's by 0.86-0.99, 0.47-
      0.53 and 1.0 of its RMS; with ``D`` 1, where ``D x`` outweighs
      ``H C``, by 0.16-0.21 each. (Under the draw before this one -
      ``D`` 1, ``W_out`` scaled like the experts' ``down``, the seven
      mixers adding 0.33 - those three controls read the served path's
      own size on the chip: a cell whose ``correct`` was blind to the
      mechanism it exists for.)

    A normed row through the router is 128 logits of deviation 1.04,
    the six chosen scores 0.85-0.95. **The correction bias** is N(0,
    0.01): the spacing of the scores about the sixth largest, so that
    it changes some choices and not all - 43 % of the (token, layer)
    decisions differ in at least one expert from the choice without
    it, 7 % of the assignments, and the busiest expert carries 1.4 of
    the mean load (a simulation of the draw, PERF.md section 6; a
    deployment's learned bias evens the load, a drawn one cannot).
    Parameter ``i`` of ``symbol.list_arguments()`` less the data inputs
    draws from ``fold_in(key, i)``."""
    names = symbol.list_arguments()
    shapes, _, _ = symbol.infer_shape(**data_shapes)
    todo = [(n, tuple(s)) for n, s in zip(names, shapes)
            if n not in data_shapes]
    dtype = jnp.dtype(cfg["param_dtype"])
    key = jax.random.PRNGKey(int(seed) % (1 << 31))
    depth = cfg.get("published", cfg)["num_hidden_layers"]
    host = {}
    for i, (name, shape) in enumerate(todo):
        kind = next((k for k in _KINDS if name.endswith(k)), "")
        arr = _drawer(kind, shape, dtype, cfg["conv_kernel"], depth)(
            jax.random.fold_in(key, i))
        host[name] = np.asarray(arr)
        arr.delete()
    return host


def _controls(cfg):
    """The controls ``LOGIT_TOL`` was set from: (key, what it is,
    ``forward``'s switches). One lowers the stated precision of the
    matmuls, four break the routed layer, two the groups of the mixer,
    one its state. ``_PER_RUN`` names those of every run's
    ``reference_detail`` line."""
    return (
        ("fp8", "the reference with every matmul operand rounded to "
         "float8_e4m3fn", {"round_to": jnp.float8_e4m3fn}),
        ("experts_out", "the same reference with the routed experts left "
         "out of every E layer (the shared expert alone)",
         {"routed": False}),
        ("relu", "the same reference with relu in place of relu squared "
         "in every expert", {"act": "relu"}),
        ("gates_unscaled", "the same reference with the chosen experts' "
         "weights not multiplied by routed_scaling_factor",
         {"scaled": False}),
        ("bias_out", "the same reference with e_score_correction_bias "
         "left out of the choice", {"choice_bias": False}),
        ("group_0", "the same reference with every head reading group "
         "0's B and C", {"one_group": True}),
        ("one_statistic", "the same reference with the gated norm's "
         "statistic over all mamba_num_heads x mamba_head_dim channels, "
         "not a group's", {"group_norm": False}),
        ("state_none", "the same reference with a state that carries "
         "nothing from one token to the next", {"state_every": 1}))


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
        "operand emulation of the served path: share of (E layer, token) "
        "routing decisions with another set of experts",
        "bfloat16_emulation_max_abs_err": float(emu_err),
        "bfloat16_emulation_max_err_over_bound": float(emu_over), **fields,
        "tolerance": LOGIT_TOL}), flush=True)
    return np.float32(0.0)


#: the controls that every run of the cell computes and prints: the one
#: that sets the limit. The others were read on twelve seeds when the
#: limit was set (``LOGIT_TOL``) and are held on the CPU by
#: chipbench/tests/test_nemotron_h.py and tests/test_nemotron_h.py; a
#: forward at the published widths is ten seconds of every run
_PER_RUN = ("fp8",)


def reference_logits(params, tokens, cfg):
    """The plain reference's logits at the positions ``check_reference``
    compares - the last ``_TAIL`` -, as ``TailLogits``. Beside them, on
    a line of its own (``reference_detail``), over the same positions:
    the reference's own bfloat16-operand emulation of the served path
    with the share of routing decisions it moves, and the controls of
    ``_PER_RUN`` against the same bound. One forward after another
    (each waits for the last: all at once do not fit beside a live
    engine)."""
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
    controls = tuple(c for c in _controls(cfg) if c[0] in _PER_RUN)
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
def _width(cfg):
    return 2 if cfg["param_dtype"] == "bfloat16" else 4


def _layers(cfg):
    """(M, *, E) layers among those that are run."""
    kinds = _reference.layer_kinds(cfg)
    return tuple(kinds.count(c) for c in "M*E")


def _mixer(cfg):
    """(H, P, N, G, d_in, conv channels)."""
    H, P, N, G = (cfg[k] for k in ("mamba_num_heads", "mamba_head_dim",
                                   "ssm_state_size", "n_groups"))
    return H, P, N, G, H * P, H * P + 2 * G * N


def kv_row_bytes(cfg):
    """One position's K and V, one attention layer (1,024 B)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * _width(cfg)


def ssm_state_bytes(cfg):
    """One read and one write of a (slot, layer)'s state, the least any
    implementation moves for a slot it advances: the float32 state
    (heads x head_dim x state) and the convolution's float32 tail
    (conv_kernel - 1 inputs of d_in + 2 x groups x state channels):
    4,341,760 B."""
    H, P, N, _G, _d_in, C = _mixer(cfg)
    return 2 * 4 * (H * P * N + (cfg["conv_kernel"] - 1) * C)


def ssm_row(cfg):
    """One real row through one mixer's recurrent part, in the chunked
    form at the published chunk Q: ``2 Q N G`` (C B^T, once a group) +
    ``2 Q P H`` (the chunk's product) + ``4 P N H`` (the incoming state
    read out, the chunk's state built) operations - 3.41 MFLOP - and
    its operands once at the stated width: ``xBC`` and ``dt`` in, ``z``
    in, the gated ``y`` out - 28,800 B."""
    H, P, N, G, d_in, C = _mixer(cfg)
    Q = cfg["chunk_size"]
    return {"flops": 2.0 * Q * N * G + 2.0 * Q * P * H + 4.0 * P * N * H,
            "bytes": (C + H + 2 * d_in) * _width(cfg)}


def moe_expert_bytes(cfg):
    """One routed expert's TWO matrices at the stated width
    (19,955,712 B)."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * _width(cfg)


def moe_assignment(cfg):
    """One (token, expert) assignment through a routed expert: its two
    products - 19.96 MFLOP - and the row in and out at the stated
    width."""
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"flops": 4.0 * D * F, "bytes": 2 * D * _width(cfg)}


def held_touched(cfg, tokens):
    """Expected held experts with at least one of ``tokens`` tokens'
    assignments under even routing (a token's choice falls on a given
    expert with k / E)."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return cfg["n_routed_experts_held"] * (1.0 - (1.0 - k / E) ** tokens)


def step(cfg, slots, step_len, live_rows):
    """One dispatch of the slot-pooled decode program. What the
    algorithm needs at the stated width: every weight outside the
    routed experts once (the mixers, the attention layers, the shared
    experts, the routers, the head), the held experts touched
    (even-routing expectation) once, the embedding rows, every fed
    slot's recurrent state read and written in every M layer
    (``ssm_state_bytes``) and a row's operands through it
    (``ssm_row``), the live K/V rows of the attention layers and the
    new rows written, float32 logits over the held vocabulary out. Pads
    count as tokens."""
    D, V, w = cfg["hidden_size"], cfg["vocab_size"], _width(cfg)
    Fs, F = cfg["moe_shared_expert_intermediate_size"], \
        cfg["moe_intermediate_size"]
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    heads, kv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    H, _P, _N, _G, d_in, C = _mixer(cfg)
    n_mamba, n_attn, n_moe = _layers(cfg)
    mamba = D * (d_in + C + H) + d_in * D + C * (cfg["conv_kernel"] + 1) \
        + 3 * H + d_in
    attn = D * (heads + 2 * kv) * dh + heads * dh * D
    outside = n_mamba * mamba + n_attn * attn \
        + n_moe * (2 * D * Fs + E * D + E) \
        + (n_mamba + n_attn + n_moe) * D + V * D + D
    tokens = slots * step_len
    here = k * cfg["n_routed_experts_held"] / E
    touched = held_touched(cfg, tokens)
    row = ssm_row(cfg)
    keys = live_rows + step_len / 2.0
    return {"flops": 2.0 * tokens * (outside + n_moe * here * 2 * D * F)
            + tokens * n_mamba * row["flops"]
            + tokens * n_attn * keys * 4.0 * heads * dh,
            "bytes": outside * w + n_moe * touched * moe_expert_bytes(cfg)
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
            "moe_assignment": moe_assignment(cfg),
            "gqa_row": {"flops": 0.0, "bytes": kv_row_bytes(cfg)}}
