"""Architecture "granite_hybrid": ``models/transformer.py``'s Granite
4.0-H decoder (``block="granite_hybrid"``: per layer a Mamba-2 mixer
whose state is constant in the context - ``ops/ssm.py`` - or grouped
attention without positions under the published multiplier -
``rtc.py``'s ``attention_decode`` -, a dense gated-SiLU feed-forward on
every layer, the four Granite multipliers, a tied head over the whole
vocabulary), served through ``serve_decoder``. The ``serve`` interface
of chipbench/README.md; the configuration's keys are the published
config.json's."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.archs.xing4 import TailLogits
from chipbench.reference import granite_hybrid as _reference

#: |served - reference| <= TOL + TOL * |reference| on every compared
#: logit. The served path holds parameters and the stream in bfloat16
#: and multiplies in bfloat16 with float32 accumulation through 40
#: layers of width 2,048; the state, the convolution's tail, the decay
#: products and every accumulation of the scan are float32. The
#: reference upcasts the same parameters and computes in float32 at the
#: highest matmul precision, the recurrence step by step. The logits
#: are small (a tied head of N(0, 0.02) rows over a unit-RMS stream,
#: divided by 8: |logit| up to 0.9), so the bound is nearly absolute.
#: The readings (my chip runs, PR 48; PERF.md, section 6; positions
#: 1,008-1,039 of two sequences - and the last 16 of 4,096 through
#: tools/window_pack_check.py -, each the largest ``err / (1 +
#: |reference|)``, linear in the bound): the served path **0.054-0.066**
#: over seven seeds, packed and whole-window program alike
#: (``max_abs_err`` 0.055-0.073); the float32 reference's own
#: bfloat16-operand emulation 0.0088-0.0091 - it rounds the matmuls'
#: operands alone, while the served path also holds the stream and every
#: product in bfloat16 through 80 residual adds (on the CPU at a width
#: of 256 the same gap: 3 to 6 times, attention layers and mamba layers
#: alike). The control that has to come out not correct, every matmul
#: operand rounded to float8_e4m3fn (the nearest precision below the
#: one stated): **0.212-0.218**, not correct on every seed. The bound
#: lies between the two with 1.8 of room on either side. Two controls
#: break the state itself (after review): a state that carries nothing
#: from token to token reads **0.31-0.43** and one dropped at every
#: multiple of ``prefill_chunk`` (the hand-over between two windows
#: lost) **0.23-0.33**, both not correct on every seed (ten seeds), so
#: the comparison does see the mixers' state. What it cannot see is the
#: state's WIDTH: rounded to bfloat16 after every token it reads
#: **0.0057-0.018** (six seeds), a tenth to a quarter of the served
#: path's own - at the decays ``make_params`` draws (0.2 to 0.999 a token)
#: and 1,040 tokens - so **the chip's comparison cannot tell a bfloat16
#: state from a float32 one**; the CPU's float32 comparison does, at a
#: decay of 0.999 and a thousand tokens (tests/test_granite_hybrid.py).
#: (The first hand-in printed 1.1e-6 for that control: the TPU compiler
#: had dropped its cast to bfloat16 and back, and the number was two
#: programs' float32 noise; reference/granite_hybrid.py::_rounder.)
#: Every run prints the emulation and the four controls on its
#: ``reference_detail`` line.
#: (The first runs ran under 0.05 and read not correct: 1.19-1.32 of
#: it.)
LOGIT_TOL = 0.12

#: positions at the end of the sequences over which the controls and
#: the emulation are compared: serve_runner.check_reference's last 16
#: of the window path and 16 of the S=1 path
_TAIL = 32


def _granite(cfg):
    from mxnet_tpu.models import transformer as tfm
    if not hasattr(tfm, "GRANITE_KEYS"):
        raise SystemExit("chipbench: this tree's models/transformer.py "
                         "builds no block 'granite_hybrid'")
    return {k: cfg[k] for k in tfm.GRANITE_KEYS}


def decode_symbol(cfg, step_len):
    """The program's own builder call. Called before any weight is
    drawn, so a tree whose ``models/transformer.py`` lacks the block
    fails here, at once."""
    from mxnet_tpu.models import transformer as tfm
    granite = _granite(cfg)
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("attention_bias") \
            or not cfg.get("tie_word_embeddings") \
            or cfg.get("normalization_function", "rmsnorm") != "rmsnorm" \
            or cfg["intermediate_size"] != cfg["shared_intermediate_size"] \
            or len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise SystemExit("chipbench: archs/granite_hybrid.py builds the "
                         "published block: silu, RMSNorm, no attention "
                         "bias, a tied head, one dense feed-forward of "
                         "shared_intermediate_size, one entry of "
                         "layer_types a layer")
    return tfm.get_decode_symbol(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], capacity=cfg["capacity"],
        per_slot=True, step_len=step_len, block="granite_hybrid",
        rms_eps=cfg["rms_norm_eps"], granite=granite)


def data_shapes(cfg, slots, step_len):
    # no positions: no pos_ids; fed: the real tokens of each slot
    return {"data": (slots, step_len), "fed": (slots,)}


def _draw(name, shape, key, taps):
    """One parameter in float32 (``make_params``)."""
    uniform = lambda lo, hi: jax.random.uniform(           # noqa: E731
        key, shape, jnp.float32, lo, hi)
    if name.endswith(("_gamma", "_mamba_D")):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("_mamba_A_log"):
        return jnp.log(uniform(1.0, 16.0))
    if name.endswith("_mamba_dt_bias"):       # softplus(dt_bias) = dt
        dt = jnp.exp(uniform(np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name.endswith(("_mamba_conv_weight", "_mamba_conv_bias")):
        bound = float(taps) ** -0.5
        return uniform(-bound, bound)
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


def make_params(symbol, data_shapes, seed, cfg):
    """Every parameter from the seed, in one jitted call, drawn in
    float32 and held at the dtype the configuration states (bfloat16).
    Matrices and the embedding N(0, 0.02), norm gains 1; the mixer's
    own parameters as Mamba-2 initialises them, so that the state is
    alive: ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of
    ``exp(U(log 1e-3, log 1e-1))`` - a token's decay ``exp(-dt A)`` then
    lies between 0.2 and 0.999 a head, memories of one to a thousand
    tokens -, ``D = 1``, and the depthwise convolution's weight and
    bias ``U(-1/sqrt(taps), 1/sqrt(taps))`` (the Conv1d default that
    Mamba-2 keeps: under N(0, 0.02) four taps would pass 4 % of their
    input and the state would weigh nothing in the output). Parameter
    ``i`` of ``symbol.list_arguments()`` less the data inputs draws
    from ``fold_in(key, i)``."""
    names = symbol.list_arguments()
    shapes, _, _ = symbol.infer_shape(**data_shapes)
    todo = [(n, tuple(s)) for n, s in zip(names, shapes)
            if n not in data_shapes]
    dtype = jnp.dtype(cfg["param_dtype"])

    def gen(key):
        return {name: _draw(name, shape, jax.random.fold_in(key, i),
                            cfg["mamba_d_conv"]).astype(dtype)
                for i, (name, shape) in enumerate(todo)}

    arrays = jax.jit(gen)(jax.random.PRNGKey(int(seed) % (1 << 31)))
    host = {}
    for name in list(arrays):
        arr = arrays.pop(name)
        host[name] = np.asarray(arr)
        arr.delete()
    return host


def _controls(cfg):
    """The controls of the ``reference_detail`` line: (key, what it is,
    ``forward``'s switches). One lowers the stated precision of the
    matmuls, one that of the state, two break the state itself."""
    return (
        ("fp8", "the reference with every matmul operand rounded to "
         "float8_e4m3fn", {"round_to": jnp.float8_e4m3fn}),
        ("state_bf16", "the same reference with the recurrent state "
         "rounded to bfloat16 after every token",
         {"state_dtype": jnp.bfloat16}),
        ("state_none", "the same reference with a state that carries "
         "nothing from one token to the next", {"state_every": 1}),
        ("state_lost", "the same reference with the state dropped at "
         "every multiple of prefill_chunk (the hand-over between two "
         "windows lost)", {"state_every": cfg["prefill_chunk"]}))


def _report(controls, emu_err, emu_over, *readings):
    fields = {}
    for i, (key, what, _switches) in enumerate(controls):
        err, over = readings[2 * i], readings[2 * i + 1]
        fields[f"{key}_control"] = what
        fields[f"{key}_control_max_abs_err"] = float(err)
        fields[f"{key}_control_max_err_over_bound"] = float(over)
        fields[f"{key}_control_correct"] = bool(over <= 1.0)
    print(json.dumps({
        "chipbench": "reference_detail", "positions_compared": _TAIL,
        "bfloat16_emulation_max_abs_err": float(emu_err),
        "bfloat16_emulation_max_err_over_bound": float(emu_over), **fields,
        "tolerance": LOGIT_TOL}), flush=True)
    return np.float32(0.0)


def reference_logits(params, tokens, cfg):
    """The plain reference's logits at the positions ``check_reference``
    compares - the last ``_TAIL`` -, as ``TailLogits`` (``np.asarray``
    of it is the ``(B, T, V)`` array with zeros before them; the head
    over 1,040 positions would be 0.8 GB of float32 that nobody reads).
    Beside them, on a line of its own (``reference_detail``), over the
    same positions: the reference's own bfloat16-operand emulation of
    the served path and the controls against the same bound. One
    forward after another (each waits for the last)."""
    T = tokens.shape[1]
    tail = min(_TAIL, T)
    ref = _reference.forward(params, tokens, cfg, tail=tail)

    def after(x):
        return jax.lax.optimization_barrier((tokens, x))[0]

    emu = _reference.forward(params, after(ref), cfg,
                             round_to=jnp.bfloat16, tail=tail)
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
        jax.ShapeDtypeStruct((), jnp.float32),
        jnp.max(emu_err), jnp.max(emu_err / bound), *readings, ordered=True)
    return TailLogits(ref + zero, T)


# ------------------------------------------------------------------ costs
def _width(cfg):
    return 2 if cfg["param_dtype"] == "bfloat16" else 4


def _layers(cfg):
    mamba = sum(k == "mamba" for k in cfg["layer_types"])
    return mamba, len(cfg["layer_types"]) - mamba


def _mixer(cfg):
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    return H, P, N, H * P, H * P + 2 * N


def kv_row_bytes(cfg):
    """One position's K and V, one attention layer (2,048 B)."""
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * dh * _width(cfg)


def ssm_state_bytes(cfg):
    """One read and one write of a (slot, layer)'s state, the least any
    implementation moves for a slot it advances: the float32 state
    (heads x head_dim x d_state) and the convolution's float32 tail
    (d_conv - 1 inputs of d_in + 2 d_state channels): 4,298,752 B."""
    H, P, N, _d_in, C = _mixer(cfg)
    return 2 * 4 * (H * P * N + (cfg["mamba_d_conv"] - 1) * C)


def ssm_row(cfg):
    """One real row through one mixer's recurrent part, in the chunked
    form at the published chunk Q: ``2 Q N`` (C B^T) + ``2 Q P H`` (the
    chunk's product) + ``4 P N H`` (the incoming state read out, the
    chunk's state built) operations - 4.26 MFLOP - and its operands
    once at the stated width: ``xBC`` and ``dt`` in, ``z`` in, the gated
    ``y`` out - 25,216 B."""
    H, P, N, d_in, C = _mixer(cfg)
    Q = cfg["mamba_chunk_size"]
    return {"flops": 2.0 * Q * N + 2.0 * Q * P * H + 4.0 * P * N * H,
            "bytes": (C + H + 2 * d_in) * _width(cfg)}


def step(cfg, slots, step_len, live_rows, rows=None):
    """One dispatch of the slot-pooled decode program: ``slots`` slots
    fed, ``rows`` real rows between them (default: every slot its
    ``step_len``). What the algorithm needs at the stated width: every
    weight once, the embedding rows, every fed slot's recurrent state
    read and written in every mamba layer (``ssm_state_bytes``), a real
    row's operands through every mixer (``ssm_row``), the live K/V rows
    of the attention layers and the new rows written, float32 logits
    over the whole vocabulary out."""
    D, V, w = cfg["hidden_size"], cfg["vocab_size"], _width(cfg)
    F = cfg["shared_intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = D // heads
    H, _P, N, d_in, C = _mixer(cfg)
    n_mamba, n_attn = _layers(cfg)
    mamba = D * (d_in + C + H) + d_in * D + C * (cfg["mamba_d_conv"] + 1) \
        + 3 * H + d_in
    attn = D * (heads + 2 * kv) * dh + heads * dh * D
    weights = n_mamba * mamba + n_attn * attn \
        + (n_mamba + n_attn) * (3 * D * F + 2 * D) + V * D + D
    tokens = slots * step_len if rows is None else rows
    row = ssm_row(cfg)
    keys = live_rows + step_len / 2.0
    return {"flops": 2.0 * tokens * weights
            + tokens * n_mamba * row["flops"]
            + tokens * n_attn * keys * 4.0 * heads * dh,
            "bytes": weights * w + tokens * D * w
            + slots * n_mamba * ssm_state_bytes(cfg)
            + tokens * n_mamba * row["bytes"]
            + n_attn * (slots * (live_rows + step_len) + tokens)
            * kv_row_bytes(cfg) + tokens * V * 4,
            "weights": weights}


def costs(cfg, slots, step_len, live_rows):
    return {"decode_step": step(cfg, slots, 1, live_rows),
            "window_step": step(cfg, slots, step_len, live_rows),
            "ssm_state": {"flops": 0.0, "bytes": ssm_state_bytes(cfg)},
            "ssm_row": ssm_row(cfg)}
