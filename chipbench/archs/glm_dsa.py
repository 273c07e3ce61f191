"""Architecture "glm_dsa": ``models/transformer.py``'s GLM-5.2 decoder
(``block="glm_dsa"``: multi-head latent attention over a latent cache
under a learned sparse selection that a period of layers shares -
``ops/mla.py`` -, a leading dense layer, then sigmoid-routed experts of
which this chip holds a share beside a shared expert - ``ops/moe.py`` -,
an untied head over a slice of the vocabulary), served through
``serve_decoder``. The ``serve`` interface of chipbench/README.md; the
configuration's keys are the published config.json's, with
``indexer_types_run`` (the layers that are run), ``n_routed_experts_held``
and ``held_first`` (the share) beside them."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import glm_dsa as _reference

#: |served - reference| <= TOL + TOL * |reference| on every compared
#: logit. The served path holds parameters and both caches in bfloat16
#: and multiplies in bfloat16 with float32 accumulation through 5
#: layers of width 6,144; the reference upcasts the same parameters and
#: computes in float32 at the highest matmul precision. Two things here
#: are discontinuous, so the bound is set from readings and not from the
#: step size: the router (a token whose 8th and 9th scores lie within
#: bfloat16's rounding goes elsewhere) and the selection - a key whose
#: index score lies within rounding of the 2,048th is swapped for
#: another. And the seeded model is sensitive to rounding as such: with
#: N(0, 0.02) weights attention is close to a mean over its keys, a
#: signal of 1 / sqrt(keys) of a value's size, and layer 0's residual
#: stream is the embedding, as small as that signal, so bfloat16's
#: rounding of it moves the dense feed-forward's input, and the logits
#: with it: over 8,364 positions (``tests/glm_long.py``) the positions
#: before ``index_topk``, where no key is dropped, read 1.46, the
#: positions behind it 2.24, and the reference's own bfloat16-operand
#: emulation 2.19. Measured on the v5e at the published
#: widths (my chip runs, PR 34; PERF.md, Findings), positions
#: 4,080-4,111 of two sequences, 16 seeds: 47 % of the (full layer,
#: sequence, query) sets differ between the float32 reference and its
#: own bfloat16-operand emulation (``set_flip_share``), and that
#: emulation reads 1.08-1.74 from the reference on logits of magnitude
#: up to 7.4-8.7; the served path reads the same, 1.20-1.60: 0.40-0.54
#: of this bound at the worst element. The two controls, each of which
#: has to come out not correct: every matmul operand rounded to
#: float8_e4m3fn (the nearest precision below the one stated) reads
#: 6.8-7.8, 2.07-2.52 times the bound; the same reference with the
#: selection left out (attention over all j <= t, twice the keys at
#: these positions) reads 7.0-7.6, 2.00-2.34 times the bound - both as
#: far from the reference as an unrelated model would be. The bound
#: lies between the readings with a factor of two on either side. Every
#: run prints the emulation and both controls on its
#: ``reference_detail`` line.
LOGIT_TOL = 2.5

#: positions at the end of the sequences over which the controls and
#: the emulation are compared: serve_runner.check_reference's last 16
#: of the window path and 16 of the S=1 path
_TAIL = 32


def _glm(cfg):
    from mxnet_tpu.models import transformer as tfm
    glm = {k: cfg[k] for k in tfm.GLM_KEYS if k != "indexer_types"}
    glm["indexer_types"] = cfg["indexer_types_run"]
    glm["held"] = (cfg["held_first"], cfg["n_routed_experts_held"])
    return glm


def _reference_cfg(cfg):
    return dict(cfg, indexer_types=cfg["indexer_types_run"])


def decode_symbol(cfg, step_len):
    """The program's own builder call. Called before any weight is
    drawn, so a tree whose ``models/transformer.py`` lacks the block
    fails here, at once (no ``GLM_KEYS``; TypeError: unexpected keyword
    ``glm``)."""
    from mxnet_tpu.models import transformer as tfm
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("attention_bias") \
            or cfg.get("tie_word_embeddings") or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["scoring_func"] != "sigmoid" \
            or not cfg["rope_interleave"] \
            or not cfg["indexer_rope_interleave"] \
            or len(cfg["indexer_types_run"]) != cfg["num_hidden_layers"]:
        raise SystemExit("chipbench: archs/glm_dsa.py builds the published "
                         "block: silu, no attention bias, an untied head, "
                         "a sigmoid router without group limit, "
                         "interleaved rotary pairs, one indexer type a "
                         "layer that is run")
    return tfm.get_decode_symbol(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], pos_embed="rotary",
        rope_base=float(cfg["rope_parameters"]["rope_theta"]),
        capacity=cfg["capacity"], per_slot=True, step_len=step_len,
        block="glm_dsa", rms_eps=cfg["rms_norm_eps"], tie_head=False,
        embed_scale=False, glm=_glm(cfg))


def data_shapes(cfg, slots, step_len):
    # rotary: no pos_ids; fed: the real tokens of each slot's step_len
    return {"data": (slots, step_len), "fed": (slots,)}


def make_params(symbol, data_shapes, seed, cfg):
    """Every parameter from the seed, in one jitted call, drawn in
    float32 and held at the dtype the configuration states (bfloat16):
    N(0, 0.02) matrices, embeddings and the router's correction bias,
    unit norm gains, a zero LayerNorm bias. Parameter ``i`` of
    ``symbol.list_arguments()`` less the data inputs draws from
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
            elif name.endswith("_beta"):
                out[name] = jnp.zeros(shape, dtype)
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


def _report(flip, emu_err, fp8_err, fp8_over, dense_err, dense_over):
    _say(positions_compared=_TAIL,
         set_flip_share=float(flip),
         sets_compared="float32 reference against its own bfloat16-"
         "operand emulation of the served path: share of (full layer, "
         "sequence, query) selections with another set of positions",
         bfloat16_emulation_max_abs_err=float(emu_err),
         control="the reference with every matmul operand rounded to "
         "float8_e4m3fn",
         control_max_abs_err=float(fp8_err),
         control_max_err_over_bound=float(fp8_over),
         control_correct=bool(fp8_over <= 1.0),
         selection_control="the same reference with the selection left "
         "out: attention over all j <= t",
         selection_control_max_abs_err=float(dense_err),
         selection_control_max_err_over_bound=float(dense_over),
         selection_control_correct=bool(dense_over <= 1.0),
         tolerance=LOGIT_TOL)
    return np.float32(0.0)


def reference_logits(params, tokens, cfg):
    """The plain reference's logits. Beside them, on a line of its own
    (``reference_detail``), over the last ``_TAIL`` positions - the
    ones ``check_reference`` compares -: the reference's own bfloat16-
    operand emulation of the served path with the share of selections
    it moves, and the two controls against the same bound. One forward
    after another (each waits for the last: four at once do not fit
    beside a live engine)."""
    rcfg = _reference_cfg(cfg)
    tail = min(_TAIL, tokens.shape[1])
    want, sets = _reference.forward(params, tokens, rcfg, return_sets=True)
    ref = want[:, -tail:]

    def after(x):
        return jax.lax.optimization_barrier((tokens, x))[0]

    emu, emu_sets = _reference.forward(
        params, after(ref), rcfg, round_to=jnp.bfloat16, tail=tail,
        return_sets=True)
    flip = _reference.set_flip_share(sets, emu_sets)
    fp8 = _reference.forward(params, after(emu), rcfg,
                             round_to=jnp.float8_e4m3fn, tail=tail)
    dense = _reference.forward(params, after(fp8), rcfg, select=False,
                               tail=tail)
    bound = LOGIT_TOL + LOGIT_TOL * jnp.abs(ref)
    fp8_err, dense_err = jnp.abs(fp8 - ref), jnp.abs(dense - ref)
    # the line is printed before the logits are handed back: the
    # callback's result is part of them
    zero = jax.experimental.io_callback(
        _report, jax.ShapeDtypeStruct((), jnp.float32), flip,
        jnp.max(jnp.abs(emu - ref)), jnp.max(fp8_err),
        jnp.max(fp8_err / bound), jnp.max(dense_err),
        jnp.max(dense_err / bound), ordered=True)
    return want + zero


# ------------------------------------------------------------------ costs
def _width(cfg):
    return 2 if cfg["param_dtype"] == "bfloat16" else 4


def latent_row_bytes(cfg):
    """One position's latent row, one layer: c_kv and k_r (1,152 B)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * _width(cfg)


def index_key_bytes(cfg):
    """One position's index key, one full layer (256 B)."""
    return cfg["index_head_dim"] * _width(cfg)


def moe_expert_bytes(cfg):
    """One routed expert's three matrices at the stated width."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * _width(cfg)


def _layers(cfg):
    kinds = cfg["indexer_types_run"]
    return len(kinds), sum(k == "full" for k in kinds), \
        len(kinds) - cfg["first_k_dense_replace"]


def held_touched(cfg, tokens):
    """Expected held experts with at least one of ``tokens`` tokens'
    assignments under even routing."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return cfg["n_routed_experts_held"] * (1.0 - (1.0 - k / E) ** tokens)


def attention(cfg, slots, step_len, live_rows):
    """What the ``mla_*`` and ``dsa_*`` kernels of one dispatch have to
    do, all layers: ``slots`` slots of ``step_len`` queries, each slot
    at context ``live_rows``. FLOPs: index scores of every query
    against the keys before it on the full layers; scores and weighted
    sums over the selected keys (at most ``index_topk``) in the
    un-absorbed widths on every layer. Bytes: the index keys scored
    once a slot; the latent rows attended - the selected ones a query
    at S = 1, the live ones once a slot in a window, whose queries'
    sets cover them -; q in and the output out; the new rows written."""
    L, full, _ = _layers(cfg)
    H, w = cfg["num_attention_heads"], _width(cfg)
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv, topk = cfg["v_head_dim"], cfg["index_topk"]
    tokens = slots * step_len
    keys = live_rows + step_len / 2.0
    chosen = min(keys, topk)
    rows = chosen if step_len == 1 else live_rows + step_len
    score = 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]
    return {"flops": full * tokens * keys * score
            + L * tokens * chosen * 2.0 * H * (dq + dv),
            "bytes": full * slots * keys * index_key_bytes(cfg)
            + L * slots * rows * latent_row_bytes(cfg)
            + L * tokens * H * (dq + dv) * w
            + tokens * (L * latent_row_bytes(cfg)
                        + full * index_key_bytes(cfg))}


def step(cfg, slots, step_len, live_rows):
    """One dispatch of the slot-pooled decode program. What the
    algorithm needs at the stated width: every weight outside the
    routed experts once, the held experts touched (even-routing
    expectation) once, the embedding rows, the state (``attention``),
    float32 logits over the held vocabulary out. Pads count as
    tokens."""
    D, V, w = cfg["hidden_size"], cfg["vocab_size"], _width(cfg)
    L, full, sparse = _layers(cfg)
    H = cfg["num_attention_heads"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    Fm, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    mla = D * qr + qr * H * dq + D * (kr + cfg["qk_rope_head_dim"]) \
        + kr * H * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]) \
        + H * cfg["v_head_dim"] * D
    indexer = qr * cfg["index_n_heads"] * cfg["index_head_dim"] \
        + D * (cfg["index_head_dim"] + cfg["index_n_heads"])
    dense_ffn = 3 * D * cfg["intermediate_size"]
    shared = 3 * D * Fm * cfg["n_shared_experts"]
    router = D * cfg["n_routed_experts"]
    outside = L * mla + full * indexer + (L - sparse) * dense_ffn \
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


def costs(cfg, slots, step_len, live_rows):
    score = 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]
    return {"decode_step": step(cfg, slots, 1, live_rows),
            "window_step": step(cfg, slots, step_len, live_rows),
            "mla_window": attention(cfg, slots, step_len, live_rows),
            "mla_row": {"flops": 0.0, "bytes": latent_row_bytes(cfg)},
            "dsa_key": {"flops": score, "bytes": index_key_bytes(cfg)},
            "moe_expert": {"flops": 0.0, "bytes": moe_expert_bytes(cfg)}}
