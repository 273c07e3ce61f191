"""Architecture "axk1": ``models/transformer.py``'s A.X-K1 decoder
(``block="axk1"``: multi-head latent attention over a latent cache with
no selection - every position at or before the query -, its rotary
under YaRN - ``ops/mla.py`` -, a leading dense layer, then sigmoid-routed
experts chosen inside the best groups, of which this chip holds a share
beside a shared expert - ``ops/moe.py`` -, an untied head over a slice
of the vocabulary), served through ``serve_decoder``. The ``serve``
interface of chipbench/README.md; the configuration's keys are the
published config.json's, with ``n_routed_experts_held`` and
``held_first`` (the share) and ``layers_run`` beside them."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import axk1 as _reference

#: |served - reference| <= TOL + TOL * |reference| on every compared
#: logit. The served path holds parameters and the latent cache in
#: bfloat16 and multiplies in bfloat16 with float32 accumulation through
#: 5 layers of width 7,168; the reference upcasts the same parameters
#: and computes in float32 at the highest matmul precision. One thing
#: here is discontinuous, so the bound is set from readings and not from
#: the step size: the router - a token whose 8th and 9th scores, or
#: whose 4th and 5th groups, lie within bfloat16's rounding goes
#: elsewhere (``choice_flip_share`` on the ``reference_detail`` line).
#: And the seeded model is sensitive to rounding as such: layer 0's
#: residual stream is the embedding, N(0, 0.02), and bfloat16's rounding
#: of it moves the dense feed-forward's input and the logits with it
#: (archs/glm_dsa.py has the same).
#: The two readings (my chip runs, PR 40; PERF.md, section 6), positions
#: 4,080-4,111 of two sequences, eight seeds, logits of magnitude up to
#: 8.2-8.9: one routing decision in nine differs between the float32
#: reference and its own bfloat16-operand emulation
#: (``choice_flip_share`` 0.106-0.113), and that emulation reads
#: 0.67-1.33 from the reference; the served path reads the same,
#: 0.59-1.32: 0.33-0.53 of this bound at the worst element. The
#: controls, each of which has to come out not correct: every matmul
#: operand rounded to float8_e4m3fn (the nearest precision below the one
#: stated) reads 4.8-5.5, 2.04-2.33 times the bound; YaRN's factor of
#: the softmax scale left out (score logits 1.81 times smaller before
#: the softmax) reads 7.2-8.0, 2.93-4.13 times; the plain rotary 9.6,
#: 3.45-3.87 times. The bound lies between the readings with a factor
#: of about two on either side. Every run prints the emulation and the
#: controls on its ``reference_detail`` line.
LOGIT_TOL = 1.8

#: positions at the end of the sequences over which the controls and
#: the emulation are compared: serve_runner.check_reference's last 16
#: of the window path and 16 of the S=1 path
_TAIL = 32


def _axk1(cfg):
    from mxnet_tpu.models import transformer as tfm
    if not hasattr(tfm, "AXK1_KEYS"):
        raise SystemExit("chipbench: this tree's models/transformer.py "
                         "builds no block 'axk1'")
    spec = {k: cfg[k] for k in tfm.AXK1_KEYS}
    spec["held"] = (cfg["held_first"], cfg["n_routed_experts_held"])
    return spec


def decode_symbol(cfg, step_len):
    """The program's own builder call. Called before any weight is
    drawn, so a tree whose ``models/transformer.py`` lacks the block
    fails here, at once."""
    from mxnet_tpu.models import transformer as tfm
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("attention_bias") \
            or cfg.get("tie_word_embeddings") \
            or cfg["scoring_func"] != "sigmoid" \
            or cfg["topk_method"] != "none" \
            or len(cfg["layers_run"]) != cfg["num_hidden_layers"]:
        raise SystemExit("chipbench: archs/axk1.py builds the published "
                         "block: silu, no attention bias, an untied head, "
                         "a sigmoid router without a correction bias "
                         "(topk_method none), one entry of layers_run a "
                         "layer that is run")
    return tfm.get_decode_symbol(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], pos_embed="rotary",
        rope_base=float(cfg["rope_theta"]), capacity=cfg["capacity"],
        per_slot=True, step_len=step_len, block="axk1",
        rms_eps=cfg["rms_norm_eps"], tie_head=False, embed_scale=False,
        axk1=_axk1(cfg))


def data_shapes(cfg, slots, step_len):
    # rotary: no pos_ids; fed: the real tokens of each slot's step_len
    return {"data": (slots, step_len), "fed": (slots,)}


def make_params(symbol, data_shapes, seed, cfg):
    """Every parameter from the seed, in one jitted call, drawn in
    float32 and held at the dtype the configuration states (bfloat16):
    N(0, 0.02) matrices and embeddings, unit norm gains. Parameter ``i``
    of ``symbol.list_arguments()`` less the data inputs draws from
    ``fold_in(key, i)``."""
    names = symbol.list_arguments()
    shapes, _, _ = symbol.infer_shape(**data_shapes)
    todo = [(n, tuple(s)) for n, s in zip(names, shapes)
            if n not in data_shapes]
    dtype = jnp.dtype(cfg["param_dtype"])

    def gen(key):
        out = {}
        for i, (name, shape) in enumerate(todo):
            if name.endswith(("_gamma", "_kv_norm_weight")):
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


#: the controls of the ``reference_detail`` line: (key, what it is,
#: ``forward``'s switches)
_CONTROLS = (
    ("fp8", "the reference with every matmul operand rounded to "
     "float8_e4m3fn", {"round_to": jnp.float8_e4m3fn}),
    ("yarn_scale", "the same reference with YaRN's factor of the softmax "
     "scale left out", {"yarn": "no_scale"}),
    ("plain_rotary", "the same reference with the plain rotary: no "
     "blended frequencies, no factor of the softmax scale",
     {"yarn": "plain"}))


def _report(flip, emu_err, *readings):
    """``readings``: each control's largest error and its largest share
    of the bound, in ``_CONTROLS``' order."""
    fields = {}
    for i, (key, what, _switches) in enumerate(_CONTROLS):
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
        "bfloat16_emulation_max_abs_err": float(emu_err), **fields,
        "tolerance": LOGIT_TOL}), flush=True)
    return np.float32(0.0)


def reference_logits(params, tokens, cfg):
    """The plain reference's logits. Beside them, on a line of its own
    (``reference_detail``), over the last ``_TAIL`` positions - the
    ones ``check_reference`` compares -: the reference's own bfloat16-
    operand emulation of the served path with the share of routing
    decisions it moves, and the controls against the same bound. One
    forward after another (each waits for the last: all at once do not
    fit beside a live engine)."""
    tail = min(_TAIL, tokens.shape[1])
    want, chosen = _reference.forward(params, tokens, cfg,
                                      return_chosen=True)
    ref = want[:, -tail:]

    def after(x):
        return jax.lax.optimization_barrier((tokens, x))[0]

    emu, emu_chosen = _reference.forward(
        params, after(ref), cfg, round_to=jnp.bfloat16, tail=tail,
        return_chosen=True)
    flip = _reference.choice_flip_share(chosen, emu_chosen)
    bound = LOGIT_TOL + LOGIT_TOL * jnp.abs(ref)
    readings, last = [], emu
    for _key, _what, switches in _CONTROLS:
        last = _reference.forward(params, after(last), cfg, tail=tail,
                                  **switches)
        err = jnp.abs(last - ref)
        readings += [jnp.max(err), jnp.max(err / bound)]
    # the line is printed before the logits are handed back: the
    # callback's result is part of them
    zero = jax.experimental.io_callback(
        _report, jax.ShapeDtypeStruct((), jnp.float32), flip,
        jnp.max(jnp.abs(emu - ref)), *readings, ordered=True)
    return want + zero


# ------------------------------------------------------------------ costs
def _width(cfg):
    return 2 if cfg["param_dtype"] == "bfloat16" else 4


def latent_row_bytes(cfg):
    """One position's latent row, one layer: c_kv and k_r (1,152 B)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * _width(cfg)


def moe_expert_bytes(cfg):
    """One routed expert's three matrices at the stated width."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * _width(cfg)


def _layers(cfg):
    L = cfg["num_hidden_layers"]
    return L, L - cfg["first_k_dense_replace"]


def held_touched(cfg, tokens):
    """Expected held experts with at least one of ``tokens`` tokens'
    assignments under even routing (a token's choice falls on a given
    expert with k / E, whatever the groups)."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return cfg["n_routed_experts_held"] * (1.0 - (1.0 - k / E) ** tokens)


def attention(cfg, slots, step_len, live_rows):
    """What the equations ask of the latent attention of one dispatch,
    all layers: ``slots`` slots of ``step_len`` queries, each slot at
    context ``live_rows``, every query over the keys at or before it.
    FLOPs: the cheaper of the two forms the equations allow, and
    ``form`` says which - *absorbed* (scores against the latent row
    itself and the weighted sum of latent rows: 2 x (kv_lora_rank +
    qk_rope_head_dim) + 2 x kv_lora_rank a query, key and head) or
    *expanded* (2 x (qk_nope + qk_rope) + 2 x v_head_dim, plus each
    key's expansion to k_n and v, 2 x kv_lora_rank x (qk_nope + v) a
    head, once a dispatch). Bytes: each slot's live rows once, q in and
    the output out, the new rows written. A kernel of the other form
    than ``form`` does more work than is counted, and its share says
    so; pads count as queries."""
    L, _ = _layers(cfg)
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
    expectation) once, the embedding rows, the state (``attention``),
    float32 logits over the held vocabulary out. Pads count as
    tokens."""
    D, V, w = cfg["hidden_size"], cfg["vocab_size"], _width(cfg)
    L, sparse = _layers(cfg)
    H = cfg["num_attention_heads"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    Fm, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    mla = D * qr + qr * H * dq + D * (kr + cfg["qk_rope_head_dim"]) \
        + kr * H * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]) \
        + H * cfg["v_head_dim"] * D
    dense_ffn = 3 * D * cfg["intermediate_size"]
    shared = 3 * D * Fm * cfg["n_shared_experts"]
    router = D * cfg["n_routed_experts"]
    outside = L * mla + (L - sparse) * dense_ffn \
        + sparse * (shared + router) + V * D
    tokens = slots * step_len
    here = k * cfg["n_routed_experts_held"] / cfg["n_routed_experts"]
    touched = held_touched(cfg, tokens)
    att = attention(cfg, slots, step_len, live_rows)
    return {"flops": 2.0 * tokens * (outside + sparse * here * 3 * D * Fm)
            + att["flops"],
            "bytes": outside * w + sparse * touched * moe_expert_bytes(cfg)
            + tokens * D * w + att["bytes"] + tokens * V * 4,
            "held_experts_touched_per_layer": touched}


def pair_costs(cfg):
    """The latent attention's work by the unit, one layer, all heads:
    a (query, key) pair in the absorbed and in the expanded form, and
    one key's expansion to k_n and v (with the latent row it reads).
    ``attention`` is these times a dispatch's pairs and keys when every
    slot is fed all its rows; a reader that knows the pairs and keys a
    dispatch really had (the ring's ``mla_pairs``, ``mla_attended``)
    takes the cheaper form of those."""
    H = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    return {"mla_pair_absorbed": {"flops": H * (2.0 * (rank + dr)
                                                + 2.0 * rank), "bytes": 0.0},
            "mla_pair_expanded": {"flops": H * (2.0 * (dn + dr) + 2.0 * dv),
                                  "bytes": 0.0},
            "mla_key_expansion": {"flops": H * 2.0 * rank * (dn + dv),
                                  "bytes": latent_row_bytes(cfg)}}


def costs(cfg, slots, step_len, live_rows):
    return {"decode_step": step(cfg, slots, 1, live_rows),
            "window_step": step(cfg, slots, step_len, live_rows),
            "mla_window": attention(cfg, slots, step_len, live_rows),
            "mla_row": {"flops": 0.0, "bytes": latent_row_bytes(cfg)},
            "moe_expert": {"flops": 0.0, "bytes": moe_expert_bytes(cfg)},
            **pair_costs(cfg)}
