#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

One process, one TPU chip, no arguments: drives the two main paths once
through the entry points a user calls, at the full width of the models
the repo documents, and checks what comes out by the repo's own means.

  device   jax.devices() must be a TPU the peaks table knows
  kernels  every registered Pallas variant, Mosaic-compiled at the smoke
           models' widths, against its XLA composition (NUMERIC_TOL)
  train    ResNet-50 / 224x224 / 1000 classes / bf16 / batch 256 for ten
           steps through examples/common/fit.py, as
           ``examples/train_imagenet.py --gpus 0 --dtype bfloat16
           --batch-size 256 --num-examples 2560`` calls it
  serve    ``mx.serve.serve_decoder`` on the documented transformer
           (docs/models.md: vocab 32000, d_model 512, 8 layers, 8 heads)
           answering 8 requests, against a one-slot KVCacheDecoder chain
  pair     Granite 4.0-H Small's block at its published widths in one
           layer pair (a Mamba-2 layer of 128 heads and the attention
           layer, each with 72 routed experts of which 36 are held
           beside the shared feed-forward): its S = 1 program and its
           packed window program compile and run once each, and a slot
           riding the window reads what the S = 1 step reads

``--multichip`` (four chips; run by hand) runs only the device check,
the train model on ``--gpus 0,1,2,3`` for three steps, and the same three
steps on one chip from the same seed as the comparison.

Every phase prints one JSON line; a failing phase ends the run non-zero
at once. The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script sets nothing about platforms and has no size option: where
JAX finds no TPU it fails at the device check. tests/test_chip_compile.py
runs the phase bodies at tiny sizes on ``mx.cpu()``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the widths of the two smoke models (and of the transformer's training
#: graph, for the variants only it reaches)
SMOKE_WIDTHS = dict(
    batch=256, classes=1000,            # ResNet-50 loss head
    conv=(256, 64, 56, 56),             # ResNet-50 stage-1 3x3 conv input
    fc=(2048, 1000),                    # its largest parameter
    slots=8, window=64,                 # decode rung x prefill chunk
    vocab=32000, d_model=512, n_head=8, capacity=1024,
    lm_batch=8, lm_seq=1024)            # docs/models.md training shape

TRAIN_ARGV = ["--network", "resnet", "--num-layers", "50",
              "--num-classes", "1000", "--dtype", "bfloat16",
              "--batch-size", "256", "--num-epochs", "1", "--lr", "0.1",
              "--mom", "0.9"]
SERVE_MODEL = dict(vocab_size=32000, d_model=512, n_layer=8, n_head=8)
#: ibm-granite/granite-4.0-h-small's config.json in two layers (a mamba
#: layer and the attention layer), this chip's half of the experts held
#: and a vocabulary of 2,048: 0.88 B parameters
GRANITE_SMALL_PAIR = dict(
    vocab_size=2048, d_model=4096, n_layer=2, n_head=32, granite=dict(
        num_key_value_heads=8, layer_types=["mamba", "attention"],
        mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128,
        mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
        mamba_chunk_size=256, mamba_conv_bias=True, mamba_proj_bias=False,
        shared_intermediate_size=1536, num_local_experts=72,
        num_experts_per_tok=10, intermediate_size=768,
        position_embedding_type="nope", embedding_multiplier=12,
        residual_multiplier=0.22, attention_multiplier=0.0078125,
        logits_scaling=16, held=(0, 36)))


def say(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _fail(phase, msg):
    raise SystemExit(f"chip_smoke[{phase}]: {msg}")


class CompileClock:
    """The seconds jax spent tracing, lowering and compiling (or reading
    the persistent cache), from jax.monitoring's own events: the running
    total, and the longest single event. One listener per process: use
    ``compile_clock()``."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.events.append(duration)

    def longest(self, start, stop=None):
        """Longest single event of ``events[start:stop]``: a program
        takes seconds, the eager one-op programs of host-side
        bookkeeping tens of milliseconds each."""
        return max(self.events[start:stop], default=0.0)


#: no single compile event may be this long once a path is warm
WARM_COMPILE_S = 0.5


@functools.cache
def compile_clock():
    return CompileClock()


# ------------------------------------------------------------------ device
def device_phase(expect_count):
    """Fail unless JAX's default backend is ``expect_count`` TPU chips of
    a kind the one peaks table knows."""
    import importlib.metadata as md
    import jax

    devs = jax.devices()
    found = [f"{d.platform}:{d.device_kind}" for d in devs]
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — jax.devices() found {found}")
    import mxnet_tpu  # noqa: F401  (wires the compile cache at import)
    from mxnet_tpu.telemetry import mfu
    if len(devs) != expect_count:
        raise SystemExit(f"chip_smoke: this run needs {expect_count} "
                         f"chip(s), jax.devices() found {found}")
    kind = devs[0].device_kind
    if mfu.device_peaks(kind) == (None, None):
        raise SystemExit(f"chip_smoke: device kind {kind!r} is not in "
                         f"telemetry.mfu.PEAKS {sorted(mfu.PEAKS)}")
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs)}
    say("device", **device,
        jax=jax.__version__, jaxlib=md.version("jaxlib"),
        libtpu=md.version("libtpu"),
        compile_cache_dir=jax.config.jax_compilation_cache_dir)
    return device


# ----------------------------------------------------------------- kernels
def kernel_sites(w, seed=0):
    """One numerics site per registered Pallas variant at widths ``w``:
    ``(name, op, raw_attrs, shapes, dtypes, is_train, inputs)``.
    ``inputs`` holds operands the gate's standard-normal synthesis cannot
    make (token ids, cache cursors, quantized weights); None elsewhere.
    """
    import numpy as np
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    bf, f32 = "bfloat16", "float32"
    B, C = w["batch"], w["classes"]
    slots, win, V, D = w["slots"], w["window"], w["vocab"], w["d_model"]
    cap = w["capacity"]
    # the decode kernel wants lane-aligned heads on the chip
    # (rtc._attention_decode_eligible): d_model as heads of 128
    dh_dec = min(128, D)
    h_dec = D // dh_dec
    dh = D // w["n_head"]
    n, cin, hh, ww = w["conv"]

    def normal(shape, dtype):
        return jnp.asarray(rs.standard_normal(shape).astype("f")).astype(
            dtype)

    def ids(shape):
        return jnp.asarray(rs.randint(0, V, shape).astype(np.int32))

    def quantized(shape, dtype):
        if dtype == "int8":
            return jnp.asarray(rs.randint(-127, 128, shape).astype(np.int8))
        return normal(shape, dtype)

    def cursors(step):
        return jnp.asarray(rs.randint(0, cap - step + 1, (slots, 1))
                           .astype(np.int32))

    sites = [
        ("softmax_ce", "SoftmaxOutput", {}, [(B, C), (B,)], [bf, f32],
         True, None),
        ("layernorm", "LayerNorm", {},
         [(slots, win, D), (D,), (D,)], [bf, f32, f32], True, None),
        ("bias_gelu", "FusedBiasGeLU", {},
         [(slots * win, 4 * D), (4 * D,)], [bf, bf], True, None),
        ("embedding", "Embedding",
         {"input_dim": V, "output_dim": D, "scale": float(np.sqrt(D))},
         [(slots, win), (V, D)], ["int32", bf], True,
         lambda: [ids((slots, win)), normal((V, D), bf)]),
        ("conv_bn_relu", "FusedConvBNReLU",
         {"kernel": (3, 3), "num_filter": cin, "pad": (1, 1),
          "fix_gamma": False},
         [w["conv"], (cin, cin, 3, 3), (cin,), (cin,), (cin,), (cin,)],
         [bf, bf, f32, f32, f32, f32], True, None),
        ("sgd_mom_update", "sgd_mom_update",
         {"lr": 0.1, "momentum": 0.9, "wd": 1e-4},
         [w["fc"]] * 3, [f32] * 3, False, None),
        ("pallas_sgd_mom_update", "pallas_sgd_mom_update",
         {"lr": 0.1, "momentum": 0.9, "wd": 1e-4},
         [w["fc"]] * 3, [f32] * 3, False, None),
        ("adam_update", "adam_update", {"lr": 0.001, "wd": 1e-4},
         [w["fc"]] * 4, [f32] * 4, False, None),
        ("attention", "attention", {"causal": True},
         [(w["lm_batch"], w["n_head"], w["lm_seq"], dh)] * 3, [bf] * 3,
         True, None),
        ("pallas_flash_attention", "pallas_flash_attention",
         {"causal": True},
         [(w["lm_batch"], w["n_head"], w["lm_seq"], dh)] * 3, [bf] * 3,
         True, None),
    ]
    for step in (1, win):
        for cache_dt in (bf, "float8_e4m3fn"):
            q = (slots, h_dec, step, dh_dec)
            kv = (slots, h_dec, cap, dh_dec)
            sites.append((
                f"attention_decode_s{step}_{cache_dt}", "attention_decode",
                {"capacity": cap, "rope": True, "per_slot": True,
                 "cache_dtype": "" if cache_dt == bf else cache_dt},
                [q, q, q, kv, kv, (slots, 1)],
                [bf, bf, bf, cache_dt, cache_dt, "int32"], False,
                lambda q=q, kv=kv, cache_dt=cache_dt, step=step: (
                    [normal(q, bf) for _ in range(3)]
                    + [normal(kv, cache_dt), normal(kv, cache_dt),
                       cursors(step)])))
    # grouped K/V heads under a window, the pools as rings (rtc.py:
    # 8 query heads on one K/V head, a window of a quarter of ``cap``
    # in a context of four times it): the S=1 read (``decode_attn``)
    # and a window of twice ``win`` rows (``window_attn`` at the smoke's
    # widths); every row fed, as for the latent ops below
    from mxnet_tpu.models.transformer import ring_rows
    swa_w = cap // 4
    swa_ring = ring_rows(swa_w, 2 * win)
    for step in (1, 2 * win):
        q, kv = (slots, 8, step, dh_dec), (slots, 1, step, dh_dec)
        ring = (slots, 1, swa_ring, dh_dec)
        sites.append((
            f"attention_decode_gqa_ring_s{step}", "attention_decode",
            {"capacity": 4 * cap, "rope": True, "per_slot": True,
             "kv_heads": 1, "window": swa_w, "ring": swa_ring, "fed": True},
            [q, kv, kv, (slots,), ring, ring, (slots, 1)],
            [bf, bf, bf, "int32", bf, bf, "int32"], False,
            lambda q=q, kv=kv, ring=ring, step=step: [
                normal(q, bf), normal(kv, bf), normal(kv, bf),
                jnp.full((slots,), step, jnp.int32), normal(ring, bf),
                normal(ring, bf),
                jnp.asarray(rs.randint(0, 4 * cap - step + 1, (slots, 1))
                            .astype(np.int32))]))
    # the routed expert feed-forward at OLMoE's own expert count, at an
    # S=1 step's rows and at a prefill window's (ragged groups, some
    # empty at S=1)
    E, top_k, F = 64, 8, 2 * D
    for label, tokens in (("s1", slots), ("window", slots * win)):
        sites.append((
            f"moe_ffn_{label}", "MoEFFN",
            {"num_experts": E, "num_hidden": F, "top_k": top_k},
            [(tokens, D), (E, D), (E, D, F), (E, D, F), (E, F, D), (4,)],
            [bf] * 5 + ["int32"], False,
            lambda tokens=tokens: [
                normal((tokens, D), bf),
                (normal((E, D), f32) / np.sqrt(D)).astype(bf),
                (normal((E, D, F), f32) / np.sqrt(D)).astype(bf),
                (normal((E, D, F), f32) / np.sqrt(D)).astype(bf),
                (normal((E, F, D), f32) / np.sqrt(F)).astype(bf),
                jnp.zeros((4,), jnp.int32)]))
    # EVA attention with its state (ops/eva.py): a window of exact rows
    # beside chunk summaries, four windows of context; ragged fed
    # counts, cursors anywhere a dispatch has room
    chunk = 16 if cap >= 1024 else 4
    for step in (1, win):
        q = (slots, h_dec, step, dh_dec)
        ring = (slots, h_dec, cap, dh_dec)
        pool = (slots, h_dec, 4 * cap // chunk, dh_dec)
        vec = (h_dec, dh_dec)
        sites.append((
            f"eva_attention_decode_s{step}", "eva_attention_decode",
            {"capacity": 4 * cap, "window": cap, "chunk": chunk,
             "rope_base": 1e5},
            [q, q, q, (slots,), vec, vec, ring, ring, pool, pool,
             (slots, 1)],
            [bf, bf, bf, "int32", bf, bf, bf, bf, bf, bf, "int32"], False,
            lambda q=q, ring=ring, pool=pool, vec=vec, step=step: (
                [normal(q, bf) for _ in range(3)]
                + [jnp.asarray(rs.randint(0, step + 1, (slots,))
                               .astype(np.int32)),
                   normal(vec, bf), normal(vec, bf),
                   normal(ring, bf), normal(ring, bf),
                   normal(pool, bf), normal(pool, bf),
                   jnp.asarray(rs.randint(0, 4 * cap - step + 1,
                                          (slots, 1)).astype(np.int32))])))
    # latent attention under a learned selection (ops/mla.py) at the
    # published head sizes where the widths allow: the indexer with a
    # top-k of the whole capacity (every position before the query is
    # selected: a threshold has no rounding to flip), the attention
    # under a random causal selection; ragged ``fed`` is the CPU tests'
    # (tests/test_glm_dsa.py)
    big = D >= 512
    Hi, di, dr = (32, 128, 64) if big else (4, 32, 16)
    dn, dv, rank = (192, 256, 512) if big else (24, 32, 64)
    lat = -(-(rank + dr) // 128) * 128
    for step in (1, win):
        # every row fed: what the kernels leave of a query block past
        # ``fed`` (zeros) and what the composition computes there are
        # both don't-cares, and the gate compares every element
        fed = lambda step=step: jnp.full((slots,), step,   # noqa: E731
                                         jnp.int32)
        sites.append((
            f"dsa_index_select_s{step}", "dsa_index_select",
            {"capacity": cap, "n_heads": Hi, "head_dim": di, "rope_dim": dr,
             "topk": cap, "rope_base": 8e6},
            [(slots, step, Hi * di), (slots, step, di), (slots, step, Hi),
             (slots,), (slots, 1, cap, di), (slots, 1)],
            [bf, bf, bf, "int32", bf, "int32"], False,
            lambda step=step, fed=fed: [
                normal((slots, step, Hi * di), bf),
                normal((slots, step, di), bf), normal((slots, step, Hi), bf),
                fed(), normal((slots, 1, cap, di), bf), cursors(step)]))

        def mla_inputs(step=step, fed=fed):
            at = cursors(step)
            seen = (np.arange(cap)[None, None, :]
                    <= np.asarray(at)[:, :, None] + np.arange(step)[None, :,
                                                                    None])
            keep = (rs.rand(slots, step, cap) < 0.5) | (np.arange(cap) == 0)
            return [normal((slots, step, h_dec * (dn + dr)), bf),
                    normal((slots, step, rank + dr), bf),
                    jnp.asarray((seen & keep).astype(np.int8)), fed(),
                    jnp.ones((rank,), bf),
                    (normal((h_dec * (dn + dv), rank), f32)
                     / np.sqrt(rank)).astype(bf),
                    normal((slots, 1, cap, lat), bf), at]
        sites.append((
            f"mla_attention_decode_s{step}", "mla_attention_decode",
            {"capacity": cap, "n_heads": h_dec, "nope_dim": dn,
             "rope_dim": dr, "v_dim": dv, "kv_rank": rank, "rope_base": 8e6},
            [(slots, step, h_dec * (dn + dr)), (slots, step, rank + dr),
             (slots, step, cap), (slots,), (rank,),
             (h_dec * (dn + dv), rank), (slots, 1, cap, lat), (slots, 1)],
            [bf, bf, "int8", "int32", bf, bf, bf, "int32"], False,
            mla_inputs))
    # hyper-connections (ops/mhc.py): the read and the join of a stream
    # of four copies of d_model, at the S = 1 program's handful of rows
    # (the mapping down the sublanes) and a window's whole tiles (along
    # the lanes); mapping weights under which its logits have Xing4.0's
    # deviation of 2.4
    for rows in (slots, slots * win):
        def stream(rows=rows):
            return [normal((1, rows, 4 * D), bf),
                    (normal((24, 4 * D), f32) * 2.4 / np.sqrt(4 * D))
                    .astype(bf), normal((24,), bf) * 0.02,
                    jnp.ones((3,), bf)]
        sites.append((
            f"mhc_pre_{rows}", "mhc_pre", {"n": 4},
            [(1, rows, 4 * D), (24, 4 * D), (24,), (3,)], [bf] * 4, False,
            stream))
        sites.append((
            f"mhc_post_{rows}", "mhc_post", {"n": 4},
            [(1, rows, 4 * D), (rows, D), (rows, 4), (rows, 16)],
            [bf, bf, f32, f32], False,
            lambda rows=rows: [
                normal((1, rows, 4 * D), bf), normal((rows, D), bf),
                jnp.abs(normal((rows, 4), f32)),
                jnp.abs(normal((rows, 16), f32)) / 4]))
    # the recurrent mixer (ops/ssm.py): heads of 64 with a state of 128
    # where the widths allow (two heads fill the state's 128 lanes), an
    # S = 1 step and a window whose slots are fed ragged counts - one
    # step for a slot fed one row, chunks for the others - over a state
    # that a last occupant left (cursor 0 reads it as zeros)
    sH, sP, sN = (2 * D // 64, 64, 128) if big else (8, D // 4, 16)
    s_in, s_c = sH * sP, sH * sP + 2 * sN
    s_w = min(128, s_in)                # ops.ssm.lane_width(sH, sP)
    for step in (1, win):
        shapes = [(slots * step, 2 * s_in + 2 * sN + sH), (slots,),
                  (s_c, 4), (s_c,), (sH,), (sH,), (sH,),
                  (slots, 3, s_c), (slots, s_in // s_w, sN, s_w), (slots, 1)]
        sites.append((
            f"ssm_mixer_decode_s{step}", "ssm_mixer_decode",
            {"heads": sH, "head_dim": sP, "d_state": sN, "d_conv": 4,
             "chunk": min(256, max(2, win // 2)), "step_len": step,
             "capacity": 4 * cap},
            shapes, [bf, "int32", bf, bf, f32, f32, f32, f32, f32, "int32"],
            False,
            lambda shapes=shapes, step=step: [
                normal(shapes[0], bf),
                jnp.asarray(rs.randint(0, step + 1, (slots,))
                            .astype(np.int32)),
                normal(shapes[2], bf) / 2, normal(shapes[3], bf) / 4,
                normal(shapes[4], f32) - 3.0,
                jnp.log(jnp.asarray(rs.uniform(1, 16, shapes[5])
                                    .astype("f"))),
                jnp.ones(shapes[6], f32), normal(shapes[7], f32),
                normal(shapes[8], f32),
                jnp.asarray(rs.randint(0, 2, (slots, 1)).astype(np.int32)
                            * rs.randint(1, cap, (slots, 1))
                            .astype(np.int32))]))
    # Kimi Delta Attention's recurrent part (ops/kda.py): heads of 128 x
    # 128 where the widths allow, an S = 1 step and a window whose slots
    # are fed ragged counts - one step of the delta rule for a slot fed
    # one row, chunks of the WY form for the others - over a state that
    # a last occupant left
    kH, kD = (D // 128, 128) if big else (4, D // 4)
    k_in = kH * kD
    for step in (1, win):
        shapes = [(slots * step, 5 * k_in + kH), (slots,), (3 * k_in, 4),
                  (kH,), (k_in,), (kD,), (slots, 3, 3 * k_in),
                  (slots, kH, kD, kD), (slots, 1)]
        sites.append((
            f"kda_mixer_decode_s{step}", "kda_mixer_decode",
            {"heads": kH, "head_dim": kD, "d_conv": 4,
             "chunk": 16 * max(1, win // 32) if win >= 16 else 2,
             "step_len": step, "capacity": 4 * cap, "lower_bound": -5.0,
             "rms_eps": 1e-6},
            shapes, [bf, "int32", bf, f32, f32, f32, f32, f32, "int32"],
            False,
            lambda shapes=shapes, step=step: [
                normal(shapes[0], bf),
                jnp.asarray(rs.randint(0, step + 1, (slots,))
                            .astype(np.int32)),
                normal(shapes[2], bf) / 2, normal(shapes[3], f32) / 4,
                4.0 * normal(shapes[4], f32) - 2.0,
                jnp.ones(shapes[5], f32), normal(shapes[6], f32),
                normal(shapes[7], f32),
                jnp.asarray(rs.randint(0, 2, (slots, 1)).astype(np.int32)
                            * rs.randint(1, cap, (slots, 1))
                            .astype(np.int32))]))
    for wdt in ("int8", "float8_e4m3fn"):
        rows, k, nh = slots * win, D, 4 * D
        sites.append((
            f"quantized_fc_{wdt}", "QuantizedFullyConnected",
            {"num_hidden": nh, "no_bias": True},
            [(rows, k), (nh, k), (nh,)], [bf, wdt, f32], False,
            lambda rows=rows, k=k, nh=nh, wdt=wdt: [
                normal((rows, k), bf), quantized((nh, k), wdt),
                jnp.abs(normal((nh,), f32)) / 127.0]))
        sites.append((
            f"quantized_conv_{wdt}", "QuantizedConvolution",
            {"kernel": (3, 3), "num_filter": cin, "pad": (1, 1),
             "no_bias": True},
            [(slots, cin, hh // 4, ww // 4), (cin, cin, 3, 3), (cin,)],
            [bf, wdt, f32], False,
            lambda wdt=wdt: [
                normal((slots, cin, hh // 4, ww // 4), bf),
                quantized((cin, cin, 3, 3), wdt),
                jnp.abs(normal((cin,), f32)) / 127.0]))
    return sites


def kernels_phase(sites, require_mosaic):
    """Run every site's ``pallas`` variant against the op's XLA
    composition through ``kernel_tier.numerics_gate``."""
    from mxnet_tpu import kernel_tier
    from mxnet_tpu.ops import pallas_kernels
    from mxnet_tpu.ops.registry import OP_REGISTRY, get_op

    if require_mosaic and pallas_kernels._interpret():
        raise SystemExit("chip_smoke: Pallas is in interpret mode on this "
                         "backend — the kernels would not be compiled")
    covered = {op for _n, op, *_ in sites}
    # "Softmax" is an alias of SoftmaxOutput (one OpDef, two names)
    registered = {nm for nm, op in OP_REGISTRY.items()
                  if "pallas" in op.variants and get_op(nm).name == nm}
    if registered - covered:
        raise SystemExit("chip_smoke: no kernel site for registered Pallas "
                         f"variant(s) {sorted(registered - covered)}")
    t0, c0 = time.perf_counter(), compile_clock().seconds
    rows, bad = [], []
    for name, op, raw_attrs, shapes, dtypes, is_train, inputs in sites:
        opdef = get_op(op)
        attrs = opdef.normalize_attrs(raw_attrs)
        if require_mosaic and not opdef.variant_eligible(
                "pallas", attrs, shapes, dtypes):
            raise SystemExit(f"chip_smoke: site {name} is not eligible for "
                             "the pallas variant on this backend")
        ok, err = kernel_tier.numerics_gate(
            opdef, attrs, shapes, dtypes, is_train=is_train,
            inputs=inputs() if inputs is not None else None)
        rows.append({"kernel": name, "ok": bool(ok),
                     "max_abs_err": float(err)})
        if not ok:
            bad.append(name)
    say("kernels", ok=not bad, compiled=not pallas_kernels._interpret(),
        kernels=rows, elapsed_s=round(time.perf_counter() - t0, 2),
        compile_s=round(compile_clock().seconds - c0, 2))
    if bad:
        raise SystemExit(f"chip_smoke: kernels outside NUMERIC_TOL of "
                         f"their XLA composition: {bad}")
    return rows


# ------------------------------------------------------------------- train
class StepWatch:
    """``batch_end_callback`` that records, per step, the loss (from the
    step's own softmax output), the clock, the program-cache compile
    count and jax's own compile seconds — and snapshots one parameter
    after the first step."""

    def __init__(self, param_name):
        self.param_name = param_name
        self.first_param = None
        self.losses, self.clock, self.compiles = [], [], []
        self.compile_s, self.compile_events = [], []

    def __call__(self, param):
        import numpy as np
        from mxnet_tpu import program_cache
        mod, batch = param.locals["self"], param.locals["batch"]
        prob = mod.get_outputs()[0].asnumpy().astype(np.float64)
        label = batch.label[0].asnumpy().astype(np.int64)
        picked = prob[np.arange(len(label)), label]
        self.losses.append(float(-np.mean(np.log(np.maximum(picked,
                                                            1e-30)))))
        self.clock.append(time.perf_counter())
        self.compiles.append(program_cache.compile_count())
        self.compile_s.append(compile_clock().seconds)
        self.compile_events.append(len(compile_clock().events))
        if self.first_param is None:
            exe = mod._exec_group.executor
            self.first_param = exe.arg_dict[self.param_name].asnumpy()


def _imagenet_example():
    """The example's own modules: ``(train_imagenet, common.fit)``."""
    for path in (os.path.join(ROOT, "examples"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    import train_imagenet
    from common import fit
    return train_imagenet, fit


def stray_buffers(mod, devices):
    """``(checked, stray)``: every buffer the armed fused step keeps —
    parameters, the batch, optimizer state, aux — must live on exactly
    ``devices``; an output may be gathered onto fewer of them. ``stray``
    maps the offenders to where they are."""
    import jax
    group = mod._exec_group
    exe = group.executor
    buffers = {f"arg:{k}": v.asjax() for k, v in exe.arg_dict.items()}
    buffers.update({f"aux:{k}": v.asjax() for k, v in exe.aux_dict.items()})
    buffers.update({f"out:{i}": o.asjax()
                    for i, o in enumerate(mod.get_outputs())})
    for nm, st in group._fused_states.items():
        for i, leaf in enumerate(jax.tree_util.tree_leaves(st)):
            buffers[f"state:{nm}:{i}"] = leaf
    want = set(devices)
    stray = {nm: sorted(str(d) for d in arr.devices())
             for nm, arr in buffers.items()
             if not (set(arr.devices()) <= want if nm.startswith("out:")
                     else set(arr.devices()) == want)}
    return len(buffers), stray


def train_phase(args, network, iters, devices, seed, phase="train"):
    """``fit.fit(args, network, iters)`` as the example calls it, watched
    step by step; asserts the fused step, placement, finite losses, no
    compile after step one, no kernel-tier error. ``devices`` is the set
    of jax devices every training buffer must live on."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import kernel_tier

    _, fit = _imagenet_example()
    mx.random.seed(seed)
    np.random.seed(seed)
    watch = StepWatch([a for a in network.list_arguments()
                       if a.endswith("_weight")][-1])
    t0, c0 = time.perf_counter(), compile_clock().seconds
    mod = fit.fit(args, network, iters, batch_end_callback=watch)
    elapsed = time.perf_counter() - t0
    compile_s = compile_clock().seconds - c0

    fail = functools.partial(_fail, phase)
    if not mod._fused_armed:
        fail("the fused train step is not armed")
    if len(watch.losses) < 2:
        fail(f"only {len(watch.losses)} step(s) ran")
    if not all(np.isfinite(watch.losses)):
        fail(f"non-finite loss: {watch.losses}")
    if watch.compiles[-1] != watch.compiles[0]:
        fail(f"compiled after the first step: program-cache count "
             f"{watch.compiles}")
    # the program cache counts traces; XLA can compile one trace again
    # when an argument's sharding changes, and only jax's clock sees it
    # (the eval program compiles after the last step, inside fit)
    late = compile_clock().longest(watch.compile_events[0],
                                   watch.compile_events[-1])
    if late > WARM_COMPILE_S:
        fail(f"jax compiled after the first step: one event of "
             f"{late:.2f}s; compile seconds by step "
             f"{[round(v - c0, 2) for v in watch.compile_s]}")
    exe = mod._exec_group.executor
    if np.array_equal(watch.first_param,
                      exe.arg_dict[watch.param_name].asnumpy()):
        fail(f"{watch.param_name} did not change after the first step")
    checked, stray = stray_buffers(mod, devices)
    if stray:
        fail(f"buffers not on {sorted(str(d) for d in devices)}: "
             f"{dict(list(stray.items())[:8])} ({len(stray)} in all)")
    errors = [d for d in kernel_tier.decisions()
              if "error" in str(d.get("reason", "")).lower()]
    if errors:
        fail(f"kernel-tier decisions with an error reason: {errors}")

    steady = np.diff(watch.clock)
    step_s = float(np.median(steady))
    say(phase, ok=True, steps=len(watch.losses),
        losses=[round(v, 4) for v in watch.losses],
        buffers_checked=checked, devices=len(devices),
        kernel_tier=[{k: d.get(k) for k in ("op", "variant", "reason")}
                     for d in kernel_tier.decisions()],
        steady_step_s=round(step_s, 4),
        img_per_s_info=round(args.batch_size / step_s, 1),
        elapsed_s=round(elapsed, 2), compile_s=round(compile_s, 2),
        first_step_compile_s=round(watch.compile_s[0] - c0, 2))
    return mod, watch


# ------------------------------------------------------------------- serve
def serve_phase(model, capacity, ladder, prompt_lens, n_requests, max_new,
                context, compute_dtype, seed, prefill_chunk=None):
    """Serve ``n_requests`` greedy requests through ``serve_decoder`` and
    hold the answers to a one-slot ``KVCacheDecoder`` chain on the same
    parameters, the way tests/test_decode_batch.py compares them."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import kernel_tier
    from mxnet_tpu.models import transformer as tfm

    fail = functools.partial(_fail, "serve")
    t0, c0 = time.perf_counter(), compile_clock().seconds
    V = model["vocab_size"]
    tol = kernel_tier.NUMERIC_TOL[str(np.dtype(compute_dtype or "float32"))]
    dev = context.jax_device()
    mx.random.seed(seed)
    np.random.seed(seed)
    rs = np.random.RandomState(seed)
    prompts = [rs.randint(0, V, rs.randint(prompt_lens[0],
                                           prompt_lens[1] + 1)).tolist()
               for _ in range(n_requests)]

    # parameters: the full-sequence graph's, initialised from the seed
    full = mx.mod.Module(
        tfm.get_symbol(seq_len=8, include_loss=False, max_seq_len=capacity,
                       **model),
        label_names=[], context=context)
    full.bind([("data", (1, 8))], None, for_training=False)
    full.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                           magnitude=2))
    args, _ = full.get_params()

    # the reference binds (and compiles) BEFORE the server warms: the
    # server's zero-compile gate reads the process-wide compile counter
    ref_mod = mx.mod.Module(
        tfm.get_decode_symbol(capacity=capacity, max_seq_len=capacity,
                              **model),
        label_names=[], context=context, compute_dtype=compute_dtype)
    # int32 ids: a float data cell would round them to the compute dtype
    ref_mod.bind([mx.io.DataDesc("data", (1, 1), np.int32)], None,
                 for_training=False)
    ref_mod.init_params(initializer=None, arg_params=args, aux_params={},
                        allow_missing=True)
    ref = tfm.KVCacheDecoder(ref_mod, capacity=capacity)

    def ref_step(tok):
        return ref.step(np.asarray([[tok]], np.int32)).asnumpy()[0, 0] \
            .astype(np.float32)

    ref_first = []
    for p in prompts:
        ref.reset()
        ref_first.append(ref_step(p[0]))

    def gen(step_len):
        return tfm.get_decode_symbol(capacity=capacity, per_slot=True,
                                     step_len=step_len,
                                     max_seq_len=capacity, **model)

    sched = mx.serve.serve_decoder(
        gen(1), args, name="chip_smoke", capacity=capacity, ladder=ladder,
        context=context, compute_dtype=compute_dtype, symbol_gen=gen,
        prefill_chunk=prefill_chunk, start=False)
    engine = sched.engine
    warm_s = time.perf_counter() - t0
    if sched.prefill_chunk <= 1:
        fail("chunked prefill is not armed")

    # first-step logits, straight off the widest rung's pooled program
    rung = engine.ladder.max
    drv = engine.driver(rung)
    first = np.zeros((rung, 1), np.int32)
    n_first = min(rung, n_requests)
    for slot in range(n_first):
        drv.join(slot)
        first[slot, 0] = prompts[slot][0]
    pooled = drv.step(first).asnumpy().astype(np.float32)
    for slot in range(n_first):
        drv.leave(slot)
    drv.rewind_many(list(range(rung)), [0] * rung)
    logit_err = 0.0
    for slot in range(n_first):
        got, want = pooled[slot, 0], ref_first[slot]
        logit_err = max(logit_err, float(np.max(np.abs(got - want))))
        if not np.allclose(got, want, rtol=tol, atol=tol):
            fail(f"request {slot}: first-step logits differ from the "
                 f"one-slot reference by {np.max(np.abs(got - want)):.4g} "
                 f"(tolerance {tol} abs+rel)")

    t1, c1 = time.perf_counter(), compile_clock().seconds
    n1 = len(compile_clock().events)
    sched.start()
    handles = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
    answers = [h.result(timeout=600) for h in handles]
    stats = sched.stats()
    sched.stop()
    serve_s = time.perf_counter() - t1
    serve_compile_s = compile_clock().seconds - c1
    serve_longest = compile_clock().longest(n1)
    for i, toks in enumerate(answers):
        if len(toks) != max_new:
            fail(f"request {i} returned {len(toks)} tokens, not {max_new}")

    # Greedy agreement with the reference chain. bf16 logits over a
    # 32000-word vocabulary tie exactly now and then (seen on the chip:
    # two ids sharing the top value), so a served token may differ from
    # the reference argmax where the reference itself scores it within
    # the dtype's tolerance of the top. The reference then follows the
    # served token, so all of an answer is checked, not just its head.
    agreed, near_ties = [], []
    for i, (p, toks) in enumerate(zip(prompts, answers)):
        ref.reset()
        for t in p[:-1]:
            ref.step(np.asarray([[t]], np.int32))
        cur, n_same, n_ties = p[-1], None, 0
        for j, served in enumerate(toks):
            lg = ref_step(cur)
            want = int(np.argmax(lg))
            if served != want:
                gap = float(lg[want] - lg[served])
                if gap > tol + tol * abs(float(lg[want])):
                    fail(f"request {i} token {j}: served {served}, the "
                         f"reference chain says {want} (logit gap "
                         f"{gap:.4g} — not a near-tie)")
                n_ties += 1
                if n_same is None:
                    n_same = j
            cur = served
        agreed.append(max_new if n_same is None else n_same)
        near_ties.append(n_ties)

    if stats["compiles_since_warmup"] != 0:
        fail(f"compiled after warm-up: {stats['compiles_since_warmup']}")
    if serve_longest > WARM_COMPILE_S:
        fail(f"jax compiled a program while serving: one event of "
             f"{serve_longest:.2f}s ({serve_compile_s:.2f}s in all)")
    mods = list(engine._bm._buckets.values()) + \
        list(engine._window_mods.values())
    cells = 0
    for mod in mods:
        exe = mod._exec_group.executor
        for nm, cell in list(exe.aux_dict.items()) + \
                list(exe.arg_dict.items()):
            cells += 1
            if set(cell.asjax().devices()) != {dev}:
                fail(f"{nm} lives on {cell.asjax().devices()}, not {dev}")
    say("serve", ok=True, requests=n_requests,
        prompt_tokens=[len(p) for p in prompts], new_tokens=max_new,
        agreed_tokens=agreed, near_ties=near_ties,
        first_logit_max_abs_err=logit_err,
        tolerance=tol, ladder=list(engine.ladder.sizes),
        windows=list(engine.window_lens),
        prefill_chunk=sched.prefill_chunk,
        warmup_compiles=engine.warmup_compiles,
        compiles_since_warmup=stats["compiles_since_warmup"],
        serving_compile_s=round(serve_compile_s, 2),
        serving_longest_compile_s=round(serve_longest, 3),
        cells_checked=cells, iterations=stats["iterations"],
        kernel_tier=sorted({(d["op"], d.get("variant"))
                            for d in kernel_tier.decisions()}),
        warmup_s=round(warm_s, 2), serve_s_info=round(serve_s, 2),
        elapsed_s=round(time.perf_counter() - t0, 2),
        compile_s=round(compile_clock().seconds - c0, 2))
    return answers


# -------------------------------------------------------------- layer pair
def layer_pair_phase(model, slots, window, capacity, context, compute_dtype,
                     seed):
    """Granite 4.0-H's block with routed experts (``GRANITE_SMALL_PAIR``)
    through ``BatchedKVCacheDecoder``: one packed window - slot 0
    prefills, every other slot rides it with one row - and, from fresh
    slots again, one S = 1 step of the same tokens. A rider at cursor 0
    and an S = 1 step at cursor 0 read the same row through two
    programs: their logits agree within the dtype's tolerance."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import kernel_tier
    from mxnet_tpu.models import transformer as tfm

    fail = functools.partial(_fail, "pair")
    t0, c0 = time.perf_counter(), compile_clock().seconds
    tol = kernel_tier.NUMERIC_TOL[str(np.dtype(compute_dtype or "float32"))]
    rs = np.random.RandomState(seed)

    def gen(step_len):
        return tfm.get_decode_symbol(capacity=capacity, per_slot=True,
                                     step_len=step_len,
                                     block="granite_hybrid", **model)

    base_sym = gen(1)
    shapes, _, _ = base_sym.infer_shape(data=(slots, 1), fed=(slots,))
    args = {}
    for name, shape in zip(base_sym.list_arguments(), shapes):
        if name in ("data", "fed"):
            continue
        if name.endswith(("_gamma", "_mamba_D")):
            draw = np.ones(shape, np.float32)
        elif name.endswith("_mamba_A_log"):
            draw = np.log(rs.uniform(1, 16, shape)).astype(np.float32)
        elif name.endswith("_mamba_dt_bias"):
            draw = (rs.standard_normal(shape) - 3.0).astype(np.float32)
        elif name.endswith(("_mamba_conv_weight", "_mamba_conv_bias")):
            draw = rs.uniform(-0.5, 0.5, shape).astype(np.float32)
        else:
            draw = (0.02 * rs.standard_normal(shape)).astype(np.float32)
        args[name] = draw

    def bound(symbol, step_len, shared=None):
        mod = mx.mod.Module(symbol, data_names=("data", "fed"),
                            label_names=[], context=context,
                            compute_dtype=compute_dtype)
        mod.bind([mx.io.DataDesc("data", (slots, step_len), np.int32),
                  mx.io.DataDesc("fed", (slots,), np.int32)],
                 None, for_training=False, shared_module=shared)
        if shared is None:
            mod.init_params(initializer=None, arg_params=args,
                            aux_params={}, allow_missing=True)
        return mod

    base = bound(base_sym, 1)
    drv = tfm.BatchedKVCacheDecoder(base, capacity, slots=slots)
    whole = gen(window)
    packed, budget = tfm.packed_window(whole, slots)
    drv.add_window(window, bound(whole, window, shared=base),
                   packed=(bound(packed, window, shared=base), budget))
    ops = [n.op for n in base_sym._topo_nodes() if not n.is_variable]
    if (ops.count("ssm_mixer_decode"), ops.count("attention_decode"),
            ops.count("MoEFFN")) != (1, 1, 2):
        fail(f"the pair is not a mamba layer and an attention layer with "
             f"routed experts: {sorted(set(ops))}")

    V = model["vocab_size"]
    tokens = rs.randint(0, V, (slots, window)).astype(np.int32)
    fed = np.ones((slots,), np.int32)
    fed[0] = min(window, budget - (slots - 1))
    for slot in range(slots):
        drv.join(slot)
    t1 = time.perf_counter()
    rode = drv.step(tokens, fed=fed).asnumpy().astype(np.float32)
    window_s = time.perf_counter() - t1
    if drv.last_program_rows != budget or rode.shape != (slots, 1, V):
        fail(f"the window ran over {drv.last_program_rows} rows (budget "
             f"{budget}) and returned {rode.shape}")
    if list(drv.pos) != list(fed):
        fail(f"cursors {list(drv.pos)} after feeding {list(fed)}")
    routed = drv.moe_stats(drv.moe_stats_begin())
    top_k = model["granite"]["num_experts_per_tok"]
    if routed["moe.assignments"] != 2 * top_k * int(fed.sum()) \
            or not 0 < routed["moe.held_assignments"] \
            < routed["moe.assignments"]:
        fail(f"the routed layers counted {routed} for {int(fed.sum())} "
             "real rows")
    for slot in range(slots):
        drv.leave(slot)
        drv.join(slot)
    t1 = time.perf_counter()
    stepped = drv.step(tokens[:, :1], fed=np.ones((slots,), np.int32)) \
        .asnumpy().astype(np.float32)
    step_s = time.perf_counter() - t1
    if not (np.isfinite(rode).all() and np.isfinite(stepped).all()):
        fail("logits are not finite")
    err = float(np.max(np.abs(rode[1:] - stepped[1:])))
    if slots > 1 and not np.allclose(rode[1:], stepped[1:], rtol=tol,
                                     atol=tol):
        fail(f"a rider's logits differ from the S = 1 step's by {err:.4g} "
             f"(tolerance {tol} abs+rel)")
    for slot in range(slots):
        drv.leave(slot)
    say("pair", ok=True, slots=slots, window=window, packed_rows=budget,
        prefill_rows=int(fed[0]), riders=slots - 1,
        rider_vs_step_max_abs_err=err, tolerance=tol,
        max_abs_logit=float(np.max(np.abs(stepped))),
        assignments=routed["moe.assignments"],
        held_assignments=routed["moe.held_assignments"],
        experts_touched=routed["moe.experts_touched"],
        state_bytes=dict(drv.state_bytes),
        parameters=int(sum(a.size for a in args.values())),
        kernel_tier=sorted({(d["op"], d.get("variant"))
                            for d in kernel_tier.decisions()}),
        window_s_info=round(window_s, 2), step_s_info=round(step_s, 2),
        elapsed_s=round(time.perf_counter() - t0, 2),
        compile_s=round(compile_clock().seconds - c0, 2))


# --------------------------------------------------------------- multichip
def multichip_phase(build, gpus, seed, rtol):
    """The train model over every chip in ``gpus`` and, from the same
    seed, on the first alone: losses agree, nothing sits on one chip,
    the compiled step all-reduces. ``build(gpus)`` returns
    ``(args, network, iters)`` for a ``--gpus`` value."""
    import numpy as np
    import mxnet_tpu as mx

    fail = functools.partial(_fail, "multichip")
    many = [mx.gpu(int(i)).jax_device() for i in gpus.split(",")]
    mod, watch = train_phase(*build(gpus), devices=many, seed=seed,
                             phase="train_multichip")
    exe = mod._exec_group.executor
    data = exe.arg_dict[mod.data_names[0]].asjax()
    shards = {s.device: s.data.shape for s in data.addressable_shards}
    if set(shards) != set(many) or \
            any(shape[0] * len(many) != data.shape[0]
                for shape in shards.values()):
        fail(f"the data batch {data.shape} is not split over the chips: "
             f"{shards}")
    hlo = mod._exec_group.lower_fused_step().compile().as_text()
    if "all-reduce" not in hlo:
        fail("the compiled fused step contains no all-reduce")
    _, single = train_phase(*build(gpus.split(",")[0]), devices=many[:1],
                            seed=seed, phase="train_one_chip")
    if not np.allclose(watch.losses, single.losses, rtol=rtol, atol=rtol):
        fail(f"per-step losses differ: {len(many)} chips {watch.losses} "
             f"vs one chip {single.losses}")
    say("multichip", ok=True, chips=len(many),
        losses_multichip=[round(v, 4) for v in watch.losses],
        losses_one_chip=[round(v, 4) for v in single.losses],
        data_shard_shapes=sorted({str(s) for s in shards.values()}),
        all_reduce_in_hlo=True)


# -------------------------------------------------------------------- main
def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--multichip", action="store_true",
                        help="four chips: the train model on --gpus "
                        "0,1,2,3 against the same steps on one chip")
    opts = parser.parse_args(argv)

    device = device_phase(expect_count=4 if opts.multichip else 1)
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import kernel_tier
    compile_clock()
    train_imagenet, _ = _imagenet_example()

    def build(gpus, examples):
        # the example shuffles its synthetic data as it builds the
        # iterators: same seed, same batches
        np.random.seed(opts.seed)
        return train_imagenet.build(
            TRAIN_ARGV + ["--gpus", gpus, "--num-examples", str(examples)])

    if opts.multichip:
        multichip_phase(lambda gpus: build(gpus, 3 * 256), "0,1,2,3",
                        opts.seed,
                        rtol=kernel_tier.NUMERIC_TOL["bfloat16"])
    else:
        kernels_phase(kernel_sites(SMOKE_WIDTHS, opts.seed),
                      require_mosaic=True)
        train_phase(*build("0", 10 * 256),
                    devices=[mx.gpu(0).jax_device()], seed=opts.seed)
        serve_phase(SERVE_MODEL, capacity=1024, ladder=[1, 4, 8],
                    prompt_lens=(16, 128), n_requests=8, max_new=32,
                    context=mx.tpu(0), compute_dtype="bfloat16",
                    seed=opts.seed)
        layer_pair_phase(GRANITE_SMALL_PAIR, slots=32, window=256,
                         capacity=1024, context=mx.tpu(0),
                         compute_dtype="bfloat16", seed=opts.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
