"""Architecture "gpt2": ``models/transformer.py``'s decoder with learned
absolute positions (pre-LayerNorm blocks, a GeLU feed-forward of four
times the width, a head tied to the token embedding), served through
``serve_decoder``. The ``serve`` interface of chipbench/README.md."""
from __future__ import annotations

from mxnet_tpu.models import transformer as tfm

from chipbench import costs as _costs, weights
from chipbench.reference import gpt2 as _reference

#: |served - reference| <= TOL + TOL * |reference| on every compared
#: logit. The served path computes in bfloat16 (8 significant bits,
#: relative step 2**-8 = 0.004) through 24 layers from float32 masters;
#: the reference is float32 at the highest matmul precision. Measured
#: on the v5e (PERF.md, Findings): max error 0.060-0.066 on logits of
#: magnitude up to 8.7, i.e. 0.43-0.46 of this bound at its worst
#: element. An 8-bit float compute path (3 significant bits, relative
#: step 0.06) is 16 times coarser: its errors near a zero logit alone
#: are several times the 0.12 allowed there.
LOGIT_TOL = 0.12


def decode_symbol(cfg, step_len):
    if cfg["n_inner"] != 4 * cfg["n_embd"]:
        raise SystemExit("chipbench: models/transformer.py fixes the "
                         "feed-forward at 4 * n_embd")
    return tfm.get_decode_symbol(
        capacity=cfg["capacity"], per_slot=True, step_len=step_len,
        max_seq_len=cfg["n_positions"], vocab_size=cfg["vocab_size"],
        d_model=cfg["n_embd"], n_layer=cfg["n_layer"],
        n_head=cfg["n_head"], pos_embed=cfg["position_embedding"])


def data_shapes(cfg, slots, step_len):
    return {"data": (slots, step_len), "pos_ids": (slots, step_len)}


def make_params(symbol, data_shapes, seed, cfg):
    """Float32, as ``Module`` binds them."""
    return weights.normal_init(symbol, data_shapes, seed)


def reference_logits(params, tokens, cfg):
    return _reference.forward(params, tokens, config=cfg)


def costs(cfg, slots, step_len, live_rows):
    return {"decode_step": _costs.gpt_step(cfg, slots, 1, live_rows),
            "window_step": _costs.gpt_step(cfg, slots, step_len, live_rows)}
