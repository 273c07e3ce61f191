"""Architecture "rotary" (a test fixture, no cell of the benchmark runs
it): ``models/transformer.py``'s decoder with rotary positions - no
``pos_ids`` input, RoPE inside ``attention_decode`` - under keys of its
own. Added by ``tests/test_archs.py`` to a copy of the benchmark as new
files only, to show that an architecture needs no edit."""
from __future__ import annotations

from mxnet_tpu import telemetry
from mxnet_tpu.models import transformer as tfm

from chipbench import weights
from chipbench.reference import rotary as _reference

#: |served - reference| <= TOL + TOL * |reference|. Two layers of width
#: 32 in bfloat16 against float32: CPU rehearsals of twelve seeds read a
#: largest error of 0.0020-0.0031 on logits up to 0.86; the program's
#: own int8 compute path (``"compute_dtype": "int8"``) reads 0.77-0.81
#: on three (PERF.md, Findings, PR 27). No device number.
LOGIT_TOL = 0.01


def decode_symbol(cfg, step_len):
    # a counter only this fixture registers, under the served model's
    # label: the runner's obs["counters"] has to carry it
    telemetry.counter("serve.decode.fixture.symbols",
                      model=cfg["name"]).inc()
    return tfm.get_decode_symbol(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], pos_embed="rotary",
        rope_base=cfg["rope_theta"], capacity=cfg["capacity"],
        step_len=step_len, per_slot=True)


def data_shapes(cfg, slots, step_len):
    return {"data": (slots, step_len)}


def make_params(symbol, data_shapes, seed, cfg):
    return weights.normal_init(symbol, data_shapes, seed,
                               cfg["param_dtype"])


def reference_logits(params, tokens, cfg):
    return _reference.forward(params, tokens, cfg)


def _step(cfg, slots, step_len, live_rows):
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    V, tokens = cfg["vocab_size"], slots * step_len
    weights_n = L * 12 * d * d + V * d
    rope = 6 * tokens * d * L               # q and k: 3 flops an element
    attn = 4 * tokens * (live_rows + step_len / 2.0) * d * L
    kv_row = 2 * d * L * 2
    return {"flops": 2 * tokens * weights_n + rope + attn,
            "bytes": 2 * weights_n + (slots * live_rows + tokens) * kv_row
            + tokens * V * 4}


def costs(cfg, slots, step_len, live_rows):
    return {"decode_step": _step(cfg, slots, 1, live_rows),
            "window_step": _step(cfg, slots, step_len, live_rows)}
