"""Architecture "sdar_moe": ``models/transformer.py``'s SDAR decoder
(``block="sdar_moe"``: 32 query heads on 4 K/V heads of 128, q and k
normed per head, rotary positions on every layer - ``rtc.py``'s
``attention_decode(block=4)``, whose mask's upper edge is the end of the
query's block -, 128 softmax-routed experts of 768 on every layer, 8 a
token, the chosen weights normed - ``ops/moe.py`` -, an unscaled
embedding and an untied head over the whole vocabulary), served through
``serve_decoder`` **by block diffusion**: the graph says that a decode
step is a block of 4 positions (``models.transformer
.decode_procedure``), and this file says it to the yardstick
(``decode_step_len``, ``mask_token``: ``manifest.ARCH_OPTIONAL``). The
``serve`` interface of chipbench/README.md; the configuration's keys are
the published config.json's, with what the file does not state
(``block_length``, ``mask_token_id``, ``denoising_steps``, ``remasking``,
``confidence_threshold``) beside them and under ``assumed``."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.reference import sdar_moe as _reference

#: |served - reference| <= TOL + TOL * |reference| on every compared
#: logit. The served path holds parameters and pools in bfloat16 and
#: multiplies in bfloat16 with float32 accumulation through 6 layers;
#: the reference upcasts the same parameters and computes in float32 at
#: the highest matmul precision. The router is discontinuous (a (layer,
#: position) whose 8th and 9th of 128 softmax probabilities lie within
#: bfloat16's rounding goes to another expert: ``routing_flip_share``
#: 7.1-8.4 %), so the bound is set from readings and not from the step
#: size. Measured on the v5e at the published widths (my chip runs, PR
#: 60; PERF.md section 6), positions 2,032-2,063 of two sequences and
#: the rows of the two fed windows, 7 seeds, every reading as max |err|
#: / (1 + |reference|), the TOL that would just pass it, on logits of
#: magnitude up to 4.7: the served path 0.117-0.157 (block steps and
#: windows), its masked feeds 0.092-0.155, the fed windows' rows
#: 0.028-0.116; the reference's own bfloat16-operand emulation
#: 0.128-0.163 - the same. The controls, which every run prints on its
#: ``reference_detail`` line: every matmul operand rounded to
#: float8_e4m3fn (the nearest precision below the one stated)
#: 1.42-1.59: not correct, 5.9-6.6 times this bound; the causal mask in
#: place of the block mask 0.356-0.422: not correct, 1.5-1.8 times.
#: The bound lies between 0.157 and 0.356, at their geometric mean,
#: because fresh seeds read higher on the served side (a maximum over
#: flipped routing decisions) and lower on the control's. **The third
#: control, a masked feed left behind (the keys and values of every
#: other block of the tail those of a feed with half its positions the
#: mask id, read at the blocks after them), reads 0.143-0.190 - inside
#: the served path's own range, so this comparison cannot tell it**:
#: four keys in two thousand moved. It is told on the CPU in float32 at
#: a small size, bit for bit (tests/test_sdar_moe.py: the pools after
#: {masked feed, take back, clean feed} equal those after the clean
#: feed alone).
LOGIT_TOL = 0.24

#: positions at the end of the sequences over which the controls and
#: the emulation are compared, and the only ones whose logits are
#: computed: serve_runner.check_reference's last 16 of the window path
#: and 16 of the block steps (and, of its second call, a chunk's last
#: row and a rider's)
_TAIL = 32


def decode_step_len(cfg):
    """The positions a slot is fed in one decode dispatch: a block."""
    return int(cfg["block_length"])


def mask_token(cfg):
    """The id that stands for a position not yet decided."""
    return int(cfg["mask_token_id"])


def _sdar(cfg):
    from mxnet_tpu.models import transformer as tfm
    return {k: cfg[k] for k in tfm.SDAR_KEYS}


def decode_symbol(cfg, step_len):
    """The program's own builder call. Called before any weight is
    drawn, so a tree whose ``models/transformer.py`` lacks the block
    fails here, at once (TypeError: unexpected keyword ``sdar``)."""
    from mxnet_tpu.models import transformer as tfm
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("attention_bias") \
            or cfg.get("tie_word_embeddings") or cfg.get("rope_scaling") \
            or cfg.get("use_sliding_window") or cfg.get("mlp_only_layers") \
            or cfg.get("decoder_sparse_step", 1) != 1:
        raise SystemExit("chipbench: archs/sdar_moe.py builds the published "
                         "block: silu, no attention bias, an untied head, "
                         "no rope scaling, no sliding window, every layer "
                         "sparse")
    return tfm.get_decode_symbol(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], pos_embed="rotary",
        rope_base=float(cfg["rope_theta"]), capacity=cfg["capacity"],
        per_slot=True, step_len=step_len, block="sdar_moe",
        rms_eps=cfg["rms_norm_eps"], tie_head=False, embed_scale=False,
        sdar=_sdar(cfg))


def data_shapes(cfg, slots, step_len):
    # rotary: no pos_ids; fed: the real tokens of each slot's step_len
    return {"data": (slots, step_len), "fed": (slots,)}


def make_params(symbol, data_shapes, seed, cfg):
    """At the dtype the configuration states (bfloat16): N(0, 0.02)
    matrices and embeddings, unit norm gains, drawn in float32 on the
    device in one jitted call - what a checkpoint of this model is, and
    what ``DecodeEngine`` then binds without a float32 master."""
    return weights.normal_init(symbol, data_shapes, seed,
                               dtype=cfg["param_dtype"])


def _left_behind(tokens, cfg, tail):
    """``(ids, read)``: ``tokens`` with every other block of the last
    ``tail`` positions fed as a masked feed feeds it (its even columns
    the mask id), and which of those positions lie in the blocks after
    such a one - where a program that did not take the feed back would
    read other keys and values than the reference's."""
    L, T = int(cfg["block_length"]), tokens.shape[1]
    at = jnp.arange(T)
    block = (at - (T - tail)) // L
    dirty = (at >= T - tail) & (block % 2 == 0)
    ids = jnp.where(dirty & (at % 2 == 0), int(cfg["mask_token_id"]), tokens)
    return ids, (~dirty)[T - tail:]


#: the controls of the ``reference_detail`` line: (key, what it is)
_CONTROLS = (
    ("fp8", "the reference with every matmul operand rounded to "
     "float8_e4m3fn"),
    ("causal", "the same reference with the causal mask in place of the "
     "block mask (a program that decodes one token a step)"),
    ("left_behind", "the same reference over ids in which every other "
     "block of the tail is a masked feed's (its even columns the mask "
     "id), read at the blocks after them: a masked feed not taken back"))


def _report(flip, emu_err, emu_over, *readings):
    """``readings``: each control's largest error and its largest share
    of the bound, in ``_CONTROLS``' order."""
    fields = {}
    for i, (key, what) in enumerate(_CONTROLS):
        err, over = readings[2 * i], readings[2 * i + 1]
        fields[f"{key}_control"] = what
        fields[f"{key}_control_max_abs_err"] = float(err)
        fields[f"{key}_control_max_err_over_bound"] = float(over)
        fields[f"{key}_control_correct"] = bool(over <= 1.0)
    print(json.dumps({
        "chipbench": "reference_detail", "positions_compared": _TAIL,
        "routing_flip_share": float(flip),
        "routing_compared": "float32 reference against its own bfloat16-"
        "operand emulation of the served path, share of (layer, sequence, "
        "position) decisions with another expert set",
        "bfloat16_emulation_max_abs_err": float(emu_err),
        "bfloat16_emulation_max_err_over_bound": float(emu_over), **fields,
        "tolerance": LOGIT_TOL}), flush=True)
    return np.float32(0.0)


@jax.tree_util.register_pytree_node_class
class TailLogits:
    """The reference's logits of the last ``n`` positions, standing for
    the ``(B, T, V)`` array of all of them: ``check_reference`` hands
    what ``reference_logits`` returns to ``np.asarray`` and slices the
    positions it compares, which are these. The head over every position
    would be 2.5 GB of float32 at the published vocabulary, beside a
    live engine, for 2,032 rows a sequence that nobody reads; as a
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
    compares - the last ``_TAIL`` -, as ``TailLogits``. Beside them, on
    a line of its own (``reference_detail``), over the same positions:
    the reference's own bfloat16-operand emulation of the served path
    with the share of routing decisions it moves, and the controls
    against the same bound. One forward after another (each waits for
    the last: all at once do not fit beside a live engine)."""
    T = tokens.shape[1]
    tail = min(_TAIL, T)
    ref, chosen = _reference.forward(params, tokens, cfg, tail=tail,
                                     return_routing=True)

    def after(x, ids=tokens):
        return jax.lax.optimization_barrier((ids, x))[0]

    emu, emu_chosen = _reference.forward(
        params, after(ref), cfg, round_to=jnp.bfloat16, tail=tail,
        return_routing=True)
    flip = _reference.routing_flip_share(chosen, emu_chosen)
    bound = LOGIT_TOL + LOGIT_TOL * jnp.abs(ref)
    fp8 = _reference.forward(params, after(emu), cfg,
                             round_to=jnp.float8_e4m3fn, tail=tail)
    causal = _reference.forward(params, after(fp8), cfg, causal=True,
                                tail=tail)
    ids, read = _left_behind(tokens, cfg, tail)
    left = _reference.forward(params, after(causal, ids), cfg, tail=tail)
    errs = [jnp.abs(fp8 - ref), jnp.abs(causal - ref),
            jnp.abs(left - ref) * read[None, :, None]]
    # the line is printed before the logits are handed back: the
    # callback's result is part of them
    emu_err = jnp.abs(emu - ref)
    zero = jax.experimental.io_callback(
        _report, jax.ShapeDtypeStruct((), jnp.float32), flip,
        jnp.max(emu_err), jnp.max(emu_err / bound),
        *[f(e) for e in errs for f in (jnp.max,
                                       lambda e: jnp.max(e / bound))],
        ordered=True)
    return TailLogits(ref + zero, T)


# ------------------------------------------------------------------ costs
def _width(cfg):
    return 2 if cfg["param_dtype"] == "bfloat16" else 4


def kv_row_bytes(cfg):
    """One position's K and V, one layer (2,048 B)."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * _width(cfg)


def moe_expert_bytes(cfg):
    """One expert's three matrices at the stated width (9.44 MB)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * _width(cfg)


def experts_touched(cfg, tokens):
    """Expected experts with at least one of ``tokens`` tokens'
    assignments under EVEN routing: 112 of 128 for the 32 rows of a
    block step of 8 slots; the measured count is the per-layer metric
    ``moe.experts_touched_per_layer_step`` and the ring's
    ``moe_touched``."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** tokens)


def step(cfg, slots, step_len, live_rows, touched=None):
    """One dispatch of the slot-pooled decode program: ``slots`` slots
    of ``step_len`` rows, each slot at context ``live_rows``. What the
    algorithm needs at the stated width: attention, router and head
    weights once, the experts touched once (``touched`` a layer; None:
    the even-routing expectation), the embedding rows, the K and V rows
    a slot's queries attend (a group's 8 query heads share their K/V
    head's rows; every row of a block attends to its block's end) and
    the new rows written, float32 logits out. Pads count as tokens."""
    D, V, w = cfg["hidden_size"], cfg["vocab_size"], _width(cfg)
    n = cfg["num_hidden_layers"]
    H, Hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    F, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
    attn = D * (H + 2 * Hkv) * dh + H * dh * D
    outside = n * (attn + D * cfg["num_experts"]) + V * D
    tokens = slots * step_len
    if touched is None:
        touched = experts_touched(cfg, tokens)
    keys = live_rows + step_len         # a block's rows see all of it
    return {"flops": 2.0 * tokens * (outside + n * k * 3 * D * F)
            + n * tokens * keys * 4.0 * H * dh,
            "bytes": outside * w + n * touched * moe_expert_bytes(cfg)
            + tokens * D * w                              # embedding rows
            + n * slots * keys * kv_row_bytes(cfg)        # cache read
            + n * tokens * kv_row_bytes(cfg)              # cache write
            + n * tokens * 2 * H * dh * w                 # q in, out out
            + tokens * V * 4,                             # logits out
            "experts_touched_per_layer": touched}


def costs(cfg, slots, step_len, live_rows):
    """``decode_step`` is the block step: the dispatch a decoding slot
    takes, ``decode_step_len`` rows a slot, under even routing.
    ``block_step_fixed`` is the same with no expert read: what
    ``layers/block_step_roofline.py`` adds the experts MEASURED as
    touched to (a block's undecided positions are all the mask id and
    route alike, so a block step touches far fewer experts than 32
    distinct rows would: 60 a layer where even routing says 112, my
    chip runs, PR 60)."""
    L = decode_step_len(cfg)
    block = step(cfg, slots, L, live_rows)
    return {"decode_step": block, "block_step": block,
            "block_step_fixed": step(cfg, slots, L, live_rows, touched=0.0),
            "window_step": step(cfg, slots, step_len, live_rows),
            "gqa_row": {"flops": 0.0, "bytes": kv_row_bytes(cfg)},
            "moe_expert": {"flops": 0.0, "bytes": moe_expert_bytes(cfg)}}
