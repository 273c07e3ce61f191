"""Architecture "xing4": ``models/transformer.py``'s Xing4.0 decoder
(``block="xing4"``: a residual stream of ``hc_mult`` copies a token, read
and joined through manifold-constrained hyper-connections - ``ops/mhc.py``
-, around multi-head latent attention with no selection under YaRN -
``ops/mla.py`` - and, after the leading dense layer, sigmoid-routed
experts chosen with a correction bias, ALL of them held, beside a shared
expert - ``ops/moe.py`` -, an untied head over the whole vocabulary),
served through ``serve_decoder``. The ``serve`` interface of
chipbench/README.md; the configuration's keys are the published
config.json's, with ``layers_run`` beside them."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.archs.axk1 import (_layers, _width, attention,  # noqa: F401
                                  latent_row_bytes, moe_expert_bytes,
                                  pair_costs)
from chipbench.reference import xing4 as _reference

#: |served - reference| <= TOL + TOL * |reference| on every compared
#: logit. The served path holds parameters, the latent cache and the
#: stream's four copies in bfloat16 and multiplies in bfloat16 with
#: float32 accumulation through 6 layers of width 3,584 (12 sub-layers,
#: each read and joined through its mapping); the reference upcasts the
#: same parameters and computes in float32 at the highest matmul
#: precision. The seeded model is chaotic under rounding, so the bound
#: is set from readings and not from the step size: the router (a token
#: whose 4th and 5th ``sc + bias`` lie within bfloat16's rounding goes
#: elsewhere, and with 4 experts a token at a scaling of 2 a flipped
#: expert is a quarter of a layer's routed sum) and the mappings, whose
#: logits have a deviation of 2.4 under the seeded weights, so that a
#: rounding of the stream moves ``Hres`` itself and a sub-layer's
#: output comes back times ``Hpost`` (up to 2). At the worst of the
#: 8.4 M compared logits the hidden state is then another token's:
#: every reading below is an error of 2 to 6 on logits whose largest is
#: 6.1-6.9, where the reference's own logit is near 0.
#: The readings (my chip runs, PR 45; PERF.md, section 6), positions
#: 4,080-4,111 of two sequences, fourteen seeds, each the largest
#: ``err / (1 + |reference|)``, which is linear in the bound: the
#: served path **2.02-3.56** (median 2.7; ``max_abs_err`` 2.3-4.2); the
#: float32 reference's own bfloat16-operand emulation 1.30-3.23, with
#: one routing decision in fifteen another set of experts
#: (``choice_flip_share`` 0.063-0.074) - the served path reads what a
#: bfloat16 path reads. The control that has to come out not correct,
#: every matmul operand rounded to float8_e4m3fn (the nearest precision
#: below the one stated): **4.82-5.67**, not correct on every seed.
#: The bound lies between the two with 1.21 and 1.12 of room: they are
#: that close because the statistic saturates (unrelated logits of this
#: spread would read about 8). Two further controls say what the
#: comparison can and cannot tell: YaRN's factor of the softmax scale
#: left out reads 4.04-5.19 (correct on 2 seeds of 14 under this
#: bound), and the mappings' own arithmetic in bfloat16 reads 0.87-3.61,
#: INSIDE the served path's range - the chip's comparison cannot tell a
#: bfloat16 mapping from a float32 one; the CPU's float32 comparison
#: does (tests/test_xing4.py: 50 tolerances there). Every run prints
#: the emulation and the controls on its ``reference_detail`` line. The
#: first runs ran under 2.0 and 3.5 (one seed of each read not correct:
#: 2.49 and 3.56); the ratios above are rescaled.
LOGIT_TOL = 4.3

#: positions at the end of the sequences over which the controls and
#: the emulation are compared: serve_runner.check_reference's last 16
#: of the window path and 16 of the S=1 path
_TAIL = 32

def _xing4(cfg):
    from mxnet_tpu.models import transformer as tfm
    if not hasattr(tfm, "XING4_KEYS"):
        raise SystemExit("chipbench: this tree's models/transformer.py "
                         "builds no block 'xing4'")
    return {k: cfg[k] for k in tfm.XING4_KEYS}


def decode_symbol(cfg, step_len):
    """The program's own builder call. Called before any weight is
    drawn, so a tree whose ``models/transformer.py`` lacks the block
    fails here, at once."""
    from mxnet_tpu.models import transformer as tfm
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("attention_bias") \
            or cfg.get("tie_word_embeddings") \
            or cfg["scoring_func"] != "sigmoid" \
            or cfg["topk_method"] != "noaux_tc" \
            or len(cfg["layers_run"]) != cfg["num_hidden_layers"]:
        raise SystemExit("chipbench: archs/xing4.py builds the published "
                         "block: silu, no attention bias, an untied head, "
                         "a sigmoid router with a correction bias "
                         "(topk_method noaux_tc), one entry of layers_run "
                         "a layer that is run")
    return tfm.get_decode_symbol(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], pos_embed="rotary",
        rope_base=float(cfg["rope_theta"]), capacity=cfg["capacity"],
        per_slot=True, step_len=step_len, block="xing4",
        rms_eps=cfg["rms_norm_eps"], tie_head=False, embed_scale=False,
        xing4=_xing4(cfg))


def data_shapes(cfg, slots, step_len):
    # rotary: no pos_ids; fed: the real tokens of each slot's step_len
    return {"data": (slots, step_len), "fed": (slots,)}


def make_params(symbol, data_shapes, seed, cfg):
    """Every parameter from the seed, in one jitted call, drawn in
    float32 and held at the dtype the configuration states (bfloat16):
    N(0, 0.02) matrices, embeddings, the router's correction bias and
    the mappings' ``W`` and ``b``, unit norm gains and unit mapping
    scales ``a`` - under which a mapping's logits have a deviation of
    0.02 x sqrt(14,336) = 2.4, so ``Hres`` is neither the identity nor
    uniform. Parameter ``i`` of ``symbol.list_arguments()`` less the
    data inputs draws from ``fold_in(key, i)``."""
    names = symbol.list_arguments()
    shapes, _, _ = symbol.infer_shape(**data_shapes)
    todo = [(n, tuple(s)) for n, s in zip(names, shapes)
            if n not in data_shapes]
    dtype = jnp.dtype(cfg["param_dtype"])

    def gen(key):
        out = {}
        for i, (name, shape) in enumerate(todo):
            if name.endswith(("_gamma", "_kv_norm_weight", "_mhc_scale")):
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
    ("mapping_bf16", "the same reference with the mappings' own "
     "arithmetic (statistic, projection, sigmoids, exp, Sinkhorn, u and "
     "X') rounded to bfloat16", {"mapping_dtype": jnp.bfloat16}),
    ("yarn_scale", "the same reference with YaRN's factor of the softmax "
     "scale left out", {"yarn": "no_scale"}))


def _report(flip, emu_err, emu_over, *readings):
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
        "bfloat16_emulation_max_abs_err": float(emu_err),
        "bfloat16_emulation_max_err_over_bound": float(emu_over), **fields,
        "tolerance": LOGIT_TOL}), flush=True)
    return np.float32(0.0)


@jax.tree_util.register_pytree_node_class
class TailLogits:
    """The reference's logits of the last ``n`` positions, standing for
    the ``(B, T, V)`` array of all of them: ``check_reference`` hands
    what ``reference_logits`` returns to ``np.asarray`` and slices the
    positions it compares, which are these. The head over every
    position would be 4.3 GB of float32 at the published sizes, beside
    a live engine, for 4,080 rows a sequence that nobody reads; as a
    pytree node the object passes through ``jax.jit`` with its one
    array, and converts to the whole array - zeros before the tail - on
    the host."""

    def __init__(self, tail, T):
        self.tail, self.T = tail, T

    def tree_flatten(self):
        return (self.tail,), self.T

    @classmethod
    def tree_unflatten(cls, T, children):
        return cls(children[0], T)

    def __array__(self, dtype=None, copy=None):
        tail = np.asarray(self.tail)
        B, n, V = tail.shape
        full = np.zeros((B, self.T, V), dtype or tail.dtype)
        full[:, self.T - n:] = tail
        return full


def reference_logits(params, tokens, cfg):
    """The plain reference's logits at the positions ``check_reference``
    compares - the last ``_TAIL`` -, as ``TailLogits``: ``np.asarray``
    of it is the ``(B, T, V)`` array with zeros before them. Beside
    them, on a line of its own
    (``reference_detail``), over the same positions: the reference's
    own bfloat16-operand emulation of the served path with the share of
    routing decisions it moves, and the controls against the same
    bound. One forward after another (each waits for the last: all at
    once do not fit beside a live engine)."""
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
    readings, last = [], emu
    for _key, _what, switches in _CONTROLS:
        last = _reference.forward(params, after(last), cfg, tail=tail,
                                  **switches)
        err = jnp.abs(last - ref)
        readings += [jnp.max(err), jnp.max(err / bound)]
    # the line is printed before the logits are handed back: the
    # callback's result is part of them
    emu_err = jnp.abs(emu - ref)
    zero = jax.experimental.io_callback(
        _report, jax.ShapeDtypeStruct((), jnp.float32), flip,
        jnp.max(emu_err), jnp.max(emu_err / bound), *readings, ordered=True)
    return TailLogits(ref + zero, T)


# ------------------------------------------------------------------ costs
# the latent attention's and an expert's are A.X-K1's, at these widths


def experts_touched(cfg, tokens):
    """Expected experts with at least one of ``tokens`` tokens'
    assignments under even routing (a token's choice falls on a given
    expert with k / E): 26 of 64 for the 8 tokens of an S = 1 step."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** tokens)


def mhc_row_bytes(cfg):
    """The least traffic of one row through one sub-layer's hyper-
    connection, a join fused with the next read: the stream read (n C),
    the sub-layer's output read (C), the stream written (n C) and the
    next read's mix written (C) at the stated width - (2n + 2) C
    numbers, 71,680 B. The mapping's 20 numbers a row stay on the chip
    in such a kernel. An implementation that reads the stream once for
    the read and once more for the join moves (3n + 2) C and reads at
    most 71 %."""
    return (2 * cfg["hc_mult"] + 2) * cfg["hidden_size"] * _width(cfg)


def step(cfg, slots, step_len, live_rows):
    """One dispatch of the slot-pooled decode program. What the
    algorithm needs at the stated width: every weight outside the
    routed experts once (the 12 mappings' among them), the experts
    touched (even-routing expectation) once, the embedding rows, the
    state (``attention``), every row through 12 hyper-connections
    (``mhc_row_bytes``), float32 logits over the whole vocabulary out.
    Pads count as tokens."""
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
    n = cfg["hc_mult"]
    mapping = (2 * n + n * n) * n * D
    outside = L * (mla + 2 * mapping) + (L - sparse) * dense_ffn \
        + sparse * (shared + router) + V * D
    tokens = slots * step_len
    touched = experts_touched(cfg, tokens)
    att = attention(cfg, slots, step_len, live_rows)
    return {"flops": 2.0 * tokens * (outside + sparse * k * 3 * D * Fm)
            + att["flops"],
            "bytes": outside * w + sparse * touched * moe_expert_bytes(cfg)
            + tokens * D * w + att["bytes"]
            + tokens * 2 * L * mhc_row_bytes(cfg) + tokens * V * 4,
            "experts_touched_per_layer": touched}


def costs(cfg, slots, step_len, live_rows):
    return {"decode_step": step(cfg, slots, 1, live_rows),
            "window_step": step(cfg, slots, step_len, live_rows),
            "mla_window": attention(cfg, slots, step_len, live_rows),
            "mla_row": {"flops": 0.0, "bytes": latent_row_bytes(cfg)},
            "mhc_row": {"flops": 0.0, "bytes": mhc_row_bytes(cfg)},
            "moe_expert": {"flops": 0.0, "bytes": moe_expert_bytes(cfg)},
            **pair_costs(cfg)}
