"""Architecture "ling_hybrid": ``models/transformer.py``'s Ling-3.0
decoder (``block="ling_hybrid"``: per layer Kimi Delta Attention, whose
state is one matrix a head under a decay a channel, constant in the
context - ``ops/kda.py`` - or latent attention over every earlier
position with a query projected directly and a head-wise output gate -
``ops/mla.py`` -, a leading dense layer, then sigmoid-routed experts
with a correction bias chosen inside the best groups, of which this
chip holds a share beside a shared expert - ``ops/moe.py`` -, an untied
head over a slice of the vocabulary), served through ``serve_decoder``.
The ``serve`` interface of chipbench/README.md; the configuration's keys
are the published config.json's, with ``num_experts_held`` and
``held_first`` (the share), ``layers_run`` and ``kda_chunk`` beside
them."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.archs.axk1 import (latent_row_bytes, moe_expert_bytes,
                                  pair_costs)
from chipbench.archs.xing4 import TailLogits
from chipbench.reference import ling_hybrid as _reference

#: |served - reference| <= TOL + TOL * |reference| on every compared
#: logit. The served path holds parameters, the stream and the latent
#: row in bfloat16 and multiplies in bfloat16 with float32 accumulation
#: through 7 layers of width 2,560; the KDA state, its tails and every
#: line of the KDA equations are float32. The reference upcasts the same
#: parameters and computes in float32 at the highest matmul precision,
#: the delta rule one token at a time. One thing is discontinuous, so
#: the bound is set from readings and not from the step size: the
#: router - 512 sigmoid scores that N(0, 0.02) router weights leave
#: within a few hundredths of 0.5, so more than one routing decision in
#: four differs between the float32 reference and its own
#: bfloat16-operand emulation (``choice_flip_share`` 0.27-0.31), and a
#: token routed elsewhere moves logits of magnitude up to 5.3 by some
#: tenths.
#: The readings (my chip runs, PR 52; PERF.md, section 6; positions
#: 1,008-1,039 of two sequences, each the largest ``err / (1 +
#: |reference|)`` over the compared logits, linear in the bound;
#: thirteen seeds): the served path **0.48-0.80** (``max_abs_err``
#: 0.44-0.80; the packed window program at 16,384 positions through
#: tools/window_pack_check.py 0.46, the whole-window program beside it
#: 0.47); the reference's own bfloat16-operand emulation 0.43-0.65 - the
#: served path is the emulation's size, the routing flips and not the
#: arithmetic set both. The control that has to come out not correct,
#: every matmul operand rounded to float8_e4m3fn (the nearest precision
#: below the one stated): **1.62-1.81**, not correct on every seed. The
#: bound lies between the two with 1.4 of room on either side. Two
#: controls break the KDA state itself: a state that carries nothing
#: from token to token reads **4.4-4.8** and one dropped at every
#: multiple of ``prefill_chunk`` (the hand-over between two windows
#: lost) **4.1-4.7**, four times the bound on every seed - the
#: comparison does see the mixers' state, and far more sharply than
#: Granite's saw its Mamba-2 state (six of seven layers read it). What
#: it cannot see is the state's WIDTH: rounded to bfloat16 after every
#: token it reads **0.61-0.95**, the served path's own size, so **the
#: chip's comparison cannot tell a bfloat16 state from a float32 one**
#: (PERF.md section 7, PR 48 found the same); the CPU's float32
#: comparison does, at a log decay of -0.001 and a thousand tokens
#: (tests/test_ling_hybrid.py). Every run prints the emulation and the
#: four controls on its ``reference_detail`` line. (The first run ran
#: under a placeholder of 0.5 and read not correct at 1.37 of it; five
#: runs ran under 1.1, the largest at 0.73 of it.)
LOGIT_TOL = 1.15

#: positions at the end of the sequences over which the controls and
#: the emulation are compared: serve_runner.check_reference's last 16
#: of the window path and 16 of the S=1 path
_TAIL = 32


def _ling(cfg):
    """``get_decode_symbol(ling=...)``: the published keys, with what is
    listed a published layer cut to the layers that are run."""
    from mxnet_tpu.models import transformer as tfm
    if not hasattr(tfm, "LING_KEYS"):
        raise SystemExit("chipbench: this tree's models/transformer.py "
                         "builds no block 'ling_hybrid'")
    run = cfg["layers_run"]
    given = {k: cfg[k] for k in tfm.LING_KEYS if k != "layer_types"}
    for k in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        given[k] = [cfg[k][i] for i in run]
    return dict(given, layer_types=_reference.layer_types(cfg),
                held=(cfg["held_first"], cfg["num_experts_held"]),
                kda_chunk=cfg["kda_chunk"])


def decode_symbol(cfg, step_len):
    """The program's own builder call. Called before any weight is
    drawn, so a tree whose ``models/transformer.py`` lacks the block
    fails here, at once."""
    from mxnet_tpu.models import transformer as tfm
    ling = _ling(cfg)
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("use_bias") \
            or cfg.get("use_qkv_bias") or cfg.get("tie_word_embeddings") \
            or cfg["score_function"] != "sigmoid" \
            or cfg["topk_method"] != "noaux_tc" \
            or cfg["num_key_value_heads"] != cfg["num_attention_heads"] \
            or cfg["rotary_dim"] != cfg["qk_rope_head_dim"] \
            or not cfg["rope_interleave"] \
            or len(cfg["layers_run"]) != cfg["num_hidden_layers"]:
        raise SystemExit("chipbench: archs/ling_hybrid.py builds the "
                         "published block: silu, no bias, an untied head, "
                         "a sigmoid router with a correction bias "
                         "(topk_method noaux_tc), as many K/V heads as "
                         "query heads, the rotary on adjacent pairs of "
                         "qk_rope_head_dim numbers, one entry of "
                         "layers_run a layer that is run")
    return tfm.get_decode_symbol(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], pos_embed="rotary",
        rope_base=float(cfg["rope_theta"]), capacity=cfg["capacity"],
        per_slot=True, step_len=step_len, block="ling_hybrid",
        rms_eps=cfg["rms_norm_eps"], tie_head=False, embed_scale=False,
        ling=ling)


def data_shapes(cfg, slots, step_len):
    # rotary: no pos_ids; fed: the real tokens of each slot's step_len
    return {"data": (slots, step_len), "fed": (slots,)}


#: ``dt_bias``' range: at ``f = 0`` and ``exp(A_log) = 1`` a channel's
#: log decay is ``-5 sigmoid(dt_bias)``, -0.0017 (a memory of 600
#: tokens) to -4.76
_DT_BIAS = (-8.0, 3.0)


@functools.lru_cache(maxsize=None)
def _drawer(kind, shape, dtype, taps):
    """One parameter of ``kind`` in float32, held at ``dtype``, from a
    key (``make_params``)."""
    def draw(key):
        uniform = lambda lo, hi: jax.random.uniform(         # noqa: E731
            key, shape, jnp.float32, lo, hi)
        if kind == "one":
            x = jnp.ones(shape, jnp.float32)
        elif kind == "A_log":
            x = jnp.log(uniform(0.5, 2.0))
        elif kind == "dt_bias":
            x = uniform(*_DT_BIAS)
        elif kind == "conv":
            x = uniform(-float(taps) ** -0.5, float(taps) ** -0.5)
        else:
            x = 0.02 * jax.random.normal(key, shape, jnp.float32)
        return x.astype(dtype)
    return jax.jit(draw)


def _kind(name):
    if name.endswith(("_gamma", "_kv_norm_weight", "_kda_norm_weight")):
        return "one"
    for kind, suffix in (("A_log", "_kda_A_log"), ("dt_bias", "_kda_dt_bias"),
                         ("conv", "_kda_conv_weight")):
        if name.endswith(suffix):
            return kind
    return "normal"


def make_params(symbol, data_shapes, seed, cfg):
    """Every parameter from the seed, drawn in float32 and held at the
    dtype the configuration states (bfloat16), one jitted call a
    parameter (10.5 GB: a parameter is on the host before the next is
    drawn). Matrices, embeddings and the router's correction bias N(0,
    0.02), norm gains 1; KDA's own so that the state is alive: a normed
    row through ``W_f`` is N(0, 1), ``A_log = log U(0.5, 2)`` and
    ``dt_bias = U(-8, 3)`` a channel, so a token's decay ``exp(-5
    sigmoid(exp(A_log) (f + dt_bias)))`` spans e^-4.8 to 0.998 - most
    of [e^-5, 1), memories of one to several hundred tokens, asserted
    below at ``f = 0`` -, ``b = sigmoid(N(0, 1))`` spans (0, 1), and the
    depthwise convolutions' weights ``U(-1/sqrt(taps), 1/sqrt(taps))``
    (archs/granite_hybrid.py's reason). Parameter ``i`` of
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
        arr = _drawer(_kind(name), shape, dtype,
                      cfg["short_conv_kernel_size"])(
            jax.random.fold_in(key, i))
        host[name] = np.asarray(arr)
        arr.delete()
    for name, bias in host.items():
        if name.endswith("_kda_dt_bias"):
            rate = np.repeat(np.exp(np.float32(
                host[name[:-len("dt_bias")] + "A_log"])), cfg["head_dim"])
            log_a = cfg["kda_lower_bound"] \
                / (1.0 + np.exp(-rate * np.float32(bias)))
            if not (log_a.min() < 0.8 * cfg["kda_lower_bound"]
                    and log_a.max() > -0.01):
                raise SystemExit(
                    f"chipbench: {name}: log decays {log_a.min():.3f} to "
                    f"{log_a.max():.4f} do not span most of "
                    f"[{cfg['kda_lower_bound']}, 0)")
    return host


def _controls(cfg):
    """The controls of the ``reference_detail`` line: (key, what it is,
    ``forward``'s switches). One lowers the stated precision of the
    matmuls, one that of the KDA state, two break the state itself."""
    return (
        ("fp8", "the reference with every matmul operand rounded to "
         "float8_e4m3fn", {"round_to": jnp.float8_e4m3fn}),
        ("state_bf16", "the same reference with the KDA state rounded to "
         "bfloat16 after every token", {"state_dtype": jnp.bfloat16}),
        ("state_none", "the same reference with a KDA state that carries "
         "nothing from one token to the next", {"state_every": 1}),
        ("state_lost", "the same reference with the KDA state dropped at "
         "every multiple of prefill_chunk (the hand-over between two "
         "windows lost)", {"state_every": cfg["prefill_chunk"]}))


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
        "operand emulation of the served path: share of (sparse layer, "
        "token) routing decisions with another set of experts",
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
def _width(cfg):
    return 2 if cfg["param_dtype"] == "bfloat16" else 4


def _layers(cfg):
    """``(kda layers, mla layers, sparse layers)`` of the layers run."""
    kinds = _reference.layer_types(cfg)
    n_kda = sum(k == "kda" for k in kinds)
    return n_kda, len(kinds) - n_kda, \
        len(kinds) - cfg["first_k_dense_replace"]


def held_touched(cfg, tokens):
    """Expected held experts with at least one of ``tokens`` tokens'
    assignments under even routing (a token's choice falls on a given
    expert with k / E, whatever the groups)."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return cfg["num_experts_held"] * (1.0 - (1.0 - k / E) ** tokens)


def kda_decode(cfg):
    """One step of one (slot, KDA layer), the least any implementation
    does for a slot it advances by a token. Operations: the
    recurrence's three ``head_dim x head_dim`` products a head - the
    erase's read ``k^T Diag(a) S``, the rank-one write ``k (x) delta``,
    the read-out ``S^T q`` -, 2 D^2 each: 3.15 MFLOP. Bytes: one read
    and one write of the float32 state (heads x D x D) and of the
    convolutions' float32 tails (K - 1 inputs of 3 H D channels):
    4,489,216 B. The decay's multiply, the norms and the row's operands
    are not counted."""
    H, D, K = (cfg[k] for k in ("num_attention_heads", "head_dim",
                                "short_conv_kernel_size"))
    return {"flops": 6.0 * H * D * D,
            "bytes": 2 * 4 * (H * D * D + (K - 1) * 3 * H * D)}


def kda_window(cfg):
    """One real row through one KDA mixer's recurrent part beyond its
    slot's state (``kda_decode``'s bytes, once a fed slot whatever its
    rows): the recurrence's same three products a head - what the
    equations ask; the chunked form's further products (the triangular
    inverse, the chunk's own scores) are its implementation's and count
    as nothing, so they lower the share and cannot raise it - and the
    row's operands once at the stated width: ``[q | k | v | f | g | b]``
    in, the gated ``o`` out: 49,216 B."""
    H, D = cfg["num_attention_heads"], cfg["head_dim"]
    return {"flops": 6.0 * H * D * D,
            "bytes": (6 * H * D + H) * _width(cfg)}


def attention(cfg, slots, step_len, live_rows):
    """What the equations ask of the latent attention of one dispatch,
    the mla layers run (archs/axk1.py's ``attention``: the cheaper of
    the absorbed and the expanded form, each slot's live rows once)."""
    _kda, L, _sparse = _layers(cfg)
    H, w = cfg["num_attention_heads"], _width(cfg)
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    tokens = slots * step_len
    keys = live_rows + step_len / 2.0
    rows = live_rows + step_len
    absorbed = tokens * keys * H * (2.0 * (rank + dr) + 2.0 * rank)
    expanded = tokens * keys * H * (2.0 * (dn + dr) + 2.0 * dv) \
        + slots * rows * H * 2.0 * rank * (dn + dv)
    return {"flops": L * min(absorbed, expanded),
            "bytes": L * slots * rows * latent_row_bytes(cfg)
            + L * tokens * H * (dn + dr + dv) * w
            + tokens * L * latent_row_bytes(cfg),
            "form": "absorbed" if absorbed <= expanded else "expanded"}


def step(cfg, slots, step_len, live_rows):
    """One dispatch of the slot-pooled decode program. What the
    algorithm needs at the stated width: every weight outside the
    routed experts once, the held experts touched (even-routing
    expectation) once, the embedding rows, every slot's KDA state read
    and written in every KDA layer and a row's operands through it
    (``kda_decode``, ``kda_window``), the latent rows (``attention``),
    float32 logits over the held vocabulary out. Pads count as
    tokens."""
    D, V, w = cfg["hidden_size"], cfg["vocab_size"], _width(cfg)
    n_kda, n_mla, sparse = _layers(cfg)
    H, dh = cfg["num_attention_heads"], cfg["head_dim"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    kr, dv = cfg["kv_lora_rank"], cfg["v_head_dim"]
    Fm, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    kda = D * (5 * H * dh + H) + H * dh * D \
        + 3 * H * dh * cfg["short_conv_kernel_size"] + H + H * dh + dh
    mla = D * H * dq + D * (kr + cfg["qk_rope_head_dim"]) + kr \
        + kr * H * (cfg["qk_nope_head_dim"] + dv) + H * dv * D + D * H
    dense_ffn = 3 * D * cfg["intermediate_size"]
    shared = 3 * D * Fm * cfg["num_shared_experts"]
    router = (D + 1) * cfg["num_experts"]
    outside = n_kda * kda + n_mla * mla \
        + (n_kda + n_mla - sparse) * dense_ffn \
        + sparse * (shared + router) + V * D
    tokens = slots * step_len
    here = k * cfg["num_experts_held"] / cfg["num_experts"]
    touched = held_touched(cfg, tokens)
    att = attention(cfg, slots, step_len, live_rows)
    state, row = kda_decode(cfg), kda_window(cfg)
    return {"flops": 2.0 * tokens * (outside + sparse * here * 3 * D * Fm)
            + n_kda * tokens * row["flops"] + att["flops"],
            "bytes": outside * w + sparse * touched * moe_expert_bytes(cfg)
            + tokens * D * w + n_kda * (slots * state["bytes"]
                                        + tokens * row["bytes"])
            + att["bytes"] + tokens * V * 4,
            "held_experts_touched_per_layer": touched}


def costs(cfg, slots, step_len, live_rows):
    return {"decode_step": step(cfg, slots, 1, live_rows),
            "window_step": step(cfg, slots, step_len, live_rows),
            "kda_decode": kda_decode(cfg), "kda_window": kda_window(cfg),
            "mla_window": attention(cfg, slots, step_len, live_rows),
            "mla_row": {"flops": 0.0, "bytes": latent_row_bytes(cfg)},
            "moe_expert": {"flops": 0.0, "bytes": moe_expert_bytes(cfg)},
            **pair_costs(cfg)}
