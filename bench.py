"""Benchmark: ResNet-50 training, framework Module.fit vs pure JAX/Flax.

The north star (BASELINE.json): >= 90% of the reference JAX/Flax
samples/sec on the same TPU chip, same operating point — bfloat16
compute over float32 master params, batch 256, SGD momentum. Both sides
run here, back to back, on the same chip:

  * ours    — `mx.mod.Module.fit` on models/resnet.get_symbol(50): the
              product hot loop (fused fwd+bwd+update XLA program ->
              buffer swaps -> metric update) over device-resident
              batches;
  * flax_ref — benchmarks/flax_resnet50.py: linen + optax with TPU best
              practices (NHWC, donated jitted train step), fully
              pre-staged device inputs.

Both sides consume device-resident data so the ratio measures the train
programs; the input pipeline (multiprocess decode + prefetch-to-device)
has its own benchmark, benchmarks/io_bench.py. The two sides are paired
at batch granularity (one forced flax step inside fit's
batch_end_callback after each forced ours batch) and the reported ratio
is the median over all paired laps.

There is no CPU path: without a TPU the script exits non-zero with the
error and prints no result. The compile cache is the library's
(mxnet_tpu/context.py: JAX_COMPILATION_CACHE_DIR, else
<checkout>/.jax_cache).

MFU is computed from each side's own compiled-program FLOPs
(`lowered.compile().cost_analysis()['flops']`) against the chip's bf16
peak — a physically-possible MFU (<= ~55% for conv nets on v5e-class)
is the sanity check the raw img/s number lacks.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
`vs_baseline` IS the ours/flax ratio (the 2017 P100 number from
reference docs/how_to/perf.md:179-188 is kept as context only).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _log(msg):
    print(f"[bench +{time.perf_counter() - _T0:.0f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()

BATCH = 256
N_BATCHES = 8          # synthetic epoch size (per timed round)
ROUNDS = 5             # interleaved A/B rounds; the reported ratio is the
                       # median over all paired laps
NUM_CLASSES = 1000
LR, MOMENTUM = 0.1, 0.9

REFERENCE_P100_IMG_S = 181.53   # context only (perf.md:179-188)


def _synthetic(rng):
    imgs = rng.rand(N_BATCHES * BATCH, 3, 224, 224).astype(np.float32)
    labels = (rng.rand(N_BATCHES * BATCH) * NUM_CLASSES).astype(
        np.float32)
    return imgs, labels


class _StagedIter:
    """Minimal DataIter over pre-staged device-resident batches.

    Both bench sides consume device-resident inputs so the ratio
    measures the train programs, not the host->device path (the
    product's staging pipeline — PrefetchingIter prefetch-to-device +
    the multiprocess decoder — has its own benchmark, io_bench.py; the
    flax referent gets the even stronger treatment of fully pre-staged
    arrays)."""

    def __init__(self, batches, provide_data, provide_label):
        self._batches = batches
        self.provide_data = provide_data
        self.provide_label = provide_label
        self.batch_size = provide_data[0].shape[0]
        self._i = 0

    def reset(self):
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self):
        if self._i >= len(self._batches):
            raise StopIteration
        b = self._batches[self._i]
        self._i += 1
        return b

    next = __next__


def setup_ours(imgs, labels):
    """Bind + compile + warm; returns (mod, staged_iter, exe, force,
    opt_params) plus the fused program's FLOPs/step."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet

    sym = resnet.get_symbol(num_classes=NUM_CLASSES, num_layers=50,
                            image_shape="3,224,224")
    it = mx.io.NDArrayIter(imgs, labels, batch_size=BATCH)
    # pin the accelerator explicitly: the default context is cpu (reference
    # semantics), which on this host would strand params on the CPU backend
    # while jnp ops land on the chip — every node a cross-device transfer
    mod = mx.mod.Module(sym, context=mx.tpu(),
                        compute_dtype=jnp.bfloat16)
    opt_params = {"learning_rate": LR, "momentum": MOMENTUM}

    _log("ours: bind+compile+warm epoch")
    mod.fit(it, num_epoch=1, initializer=mx.initializer.Xavier(),
            optimizer_params=opt_params)
    assert mod._fused_armed, "bench must measure the fused train step"
    exe = mod._exec_group.executor

    _log("ours: staging batches on device")
    it.reset()
    dev = mx.tpu().jax_device()
    staged = []
    for b in it:
        arrs = [mx.nd.NDArray(jax.device_put(a.asjax(), dev))
                for a in b.data]
        labs = [mx.nd.NDArray(jax.device_put(a.asjax(), dev))
                for a in (b.label or [])]
        for a in arrs + labs:
            jax.block_until_ready(a.asjax())
        staged.append(mx.io.DataBatch(arrs, labs, pad=b.pad))
    staged_it = _StagedIter(staged, it.provide_data, it.provide_label)

    def force(param=None):
        # Device-side metrics no longer sync per batch (metric.py
        # _accumulate_device), so force completion by fetching the
        # metric's pending device scalar — 4 bytes, one round trip,
        # exactly symmetric with the flax side's loss fetch. Fall back
        # to an output fetch if the metric has nothing pending.
        m = getattr(param, "eval_metric", None) if param else None
        if m is not None and getattr(m, "_pending", None):
            float(jax.device_get(m._pending[-1][0]))
        else:
            jax.device_get(exe._outputs[0].asjax())

    flops = None
    try:
        cost = mod._exec_group.lower_fused_step().compile().cost_analysis()
        if cost and "flops" in cost:
            flops = float(cost["flops"])
    except Exception as e:
        _log(f"ours: cost_analysis unavailable: {e!r}")
    return (mod, staged_it, exe, force, opt_params), flops


def setup_flax(imgs, labels):
    """Compile + warm; returns a one-forced-step closure."""
    import jax
    from benchmarks.flax_resnet50 import make_train_step

    step, init = make_train_step(BATCH, LR, MOMENTUM, NUM_CLASSES)
    state_box = [init(jax.random.PRNGKey(0))]
    nhwc = np.ascontiguousarray(imgs.transpose(0, 2, 3, 1))
    lab = labels.astype(np.int32)

    def batch(i):
        j = (i % N_BATCHES) * BATCH
        return nhwc[j:j + BATCH], lab[j:j + BATCH]

    flops = None
    try:
        _log("flax: lower+compile")
        cost = step.lower(state_box[0],
                          *batch(0)).compile().cost_analysis()
        if cost and "flops" in cost:
            flops = float(cost["flops"])
    except Exception as e:
        # cost_analysis is best-effort across jax versions, but a failure
        # must be visible — a silent null here hid a NameError for a round
        _log(f"flax: cost_analysis unavailable: {e!r}")

    _log("flax: warm steps + device staging")
    staged = []
    for i in range(N_BATCHES):
        x, y = batch(i)
        xd, yd = jax.device_put(x), jax.device_put(y)
        jax.block_until_ready(xd)
        staged.append((xd, yd))
    for i in range(3):                      # compile + warm
        state_box[0], loss = step(state_box[0], *staged[i % N_BATCHES])
    float(jax.device_get(loss))

    counter = [0]                           # device-step submissions

    def one_step(i):
        # forced completion via scalar fetch, symmetric with ours' metric
        # fetch
        state_box[0], loss = step(state_box[0],
                                  *staged[i % N_BATCHES])
        counter[0] += 1           # timed laps only (warm calls step())
        float(jax.device_get(loss))

    return one_step, flops, counter


def measure_spmd_variant():
    """The ``spmd`` variant row: paired spmd-vs-kvstore lap on the
    local mesh (benchmarks/spmd_vs_kvstore.py), attached to the bench
    JSON so the MULTICHIP series tracks the GSPMD path. Needs >= 2
    devices (one device has no gradient collective to compare); returns
    a skip note otherwise. Run AFTER the main paired laps — it compiles
    and trains its own programs."""
    import jax
    try:
        if len(jax.devices()) < 2:
            return {"skipped": f"{len(jax.devices())} device(s); the "
                    "spmd-vs-kvstore pairing needs a multi-device mesh"}
        from benchmarks.spmd_vs_kvstore import main as spmd_lap
        return spmd_lap(quiet=True)
    except Exception as e:          # the variant must never sink the run
        return {"error": f"{type(e).__name__}: {e}"}


def measure_serve_variant():
    """The ``serve`` variant row: req/s at a p99 SLO under an open-loop
    Poisson load against the continuous-batching server (mxnet_tpu/
    serve) — the second bench axis ROADMAP item 3 names, next to
    img/s. A small MLP keeps the serving overheads (scheduler, pad/
    slice, dispatch) the measured quantity rather than model FLOPs;
    Never sinks the run."""
    import numpy as np
    import mxnet_tpu as mx

    SLO_MS = 100
    try:
        data = mx.sym.var("data")
        fc = mx.sym.FullyConnected(data=data, num_hidden=64, name="sv1")
        act = mx.sym.Activation(fc, act_type="relu")
        fc2 = mx.sym.FullyConnected(act, num_hidden=16, name="sv2")
        sym = mx.sym.SoftmaxOutput(fc2, name="softmax")
        mod = mx.mod.Module(sym, context=mx.tpu())
        mod.bind([("data", (8, 32))], [("softmax_label", (8,))],
                 for_training=False)
        mod.init_params(mx.initializer.Xavier())
        server = mx.serve.serve(mod, name="bench", ladder=[1, 2, 4, 8],
                                default_deadline_ms=SLO_MS)
        gen = mx.serve.PoissonLoadGen(
            server,
            lambda i, rng: {"data": rng.rand(1 + i % 3, 32)
                            .astype(np.float32)},
            model="bench", rate=150.0, n_requests=300, seed=0)
        try:
            out = gen.run(slo_ms=SLO_MS)
        finally:
            server.stop()
        stats = server.stats()
        m = stats["models"]["bench"]
        out.update({
            "batch_occupancy": m["batch_occupancy"],
            "padding_waste_pct": m["padding_waste_pct"],
            "dispatches": m["dispatches"],
            "compiles_since_warmup": stats["compiles_since_warmup"],
            "ladder": m["ladder"],
        })
        return out
    except Exception as e:          # the variant must never sink the run
        return {"error": f"{type(e).__name__}: {e}"}


def measure_quant_serve_variant():
    """The ``quant`` serve variant row: req/s at the p99 SLO through the
    continuous-batching server, int8 ladder vs the float ladder, same
    model/load — the int8 inference tier's capacity multiplier
    (ROADMAP 4). The int8 engine binds the quantized graph
    (``compute_dtype="int8"`` → ops/quant.py rewrite), so its rungs pin
    quantized programs; the ``compiles_since_warmup == 0`` contract is
    asserted per side. Runs on whatever backend the process has (the
    dequant-fused Pallas kernel is autotuned on TPU, interpret-gated
    off it). Never sinks the run."""
    import numpy as np
    import mxnet_tpu as mx

    SLO_MS = 100

    def one_side(compute_dtype, tag):
        data = mx.sym.var("data")
        fc = mx.sym.FullyConnected(data=data, num_hidden=256, name="qv1")
        act = mx.sym.Activation(fc, act_type="relu")
        fc2 = mx.sym.FullyConnected(act, num_hidden=64, name="qv2")
        sym = mx.sym.SoftmaxOutput(fc2, name="softmax")
        mod = mx.mod.Module(sym, context=mx.tpu())
        mod.bind([("data", (8, 64))], [("softmax_label", (8,))],
                 for_training=False)
        mod.init_params(mx.initializer.Xavier())
        server = mx.serve.serve(mod, name=tag, ladder=[1, 2, 4, 8],
                                default_deadline_ms=SLO_MS,
                                compute_dtype=compute_dtype)
        gen = mx.serve.PoissonLoadGen(
            server,
            lambda i, rng: {"data": rng.rand(1 + i % 3, 64)
                            .astype(np.float32)},
            model=tag, rate=150.0, n_requests=200, seed=0)
        try:
            out = gen.run(slo_ms=SLO_MS)
        finally:
            server.stop()
        stats = server.stats()
        out["compiles_since_warmup"] = stats["compiles_since_warmup"]
        out["quantized"] = stats["models"][tag]["quantized"]
        return out

    try:
        base = one_side(None, "qbase")
        int8 = one_side("int8", "qint8")
        row = {"float": base, "int8": int8}
        if base.get("req_per_sec") and int8.get("req_per_sec"):
            row["int8_speedup"] = round(
                int8["req_per_sec"] / base["req_per_sec"], 3)
        return row
    except Exception as e:          # the variant must never sink the run
        return {"error": f"{type(e).__name__}: {e}"}


def measure_lm_variant():
    """The ``lm`` variant row: the transformer workload's three axes
    (ROADMAP 1) — training tokens/s + step time through the fused
    Module.fit path, incremental KV-cache decode tokens/s, and a
    max-context-length sweep that walks the context up until the static
    memory planner's ME801 predicted-OOM trips against the device HBM
    capacity. Also attaches the kernel-tier selection table filtered to
    the attention family, so the xla/flash/ring pick per shape lands in
    the payload. Never sinks the run."""
    import time
    import numpy as np
    import mxnet_tpu as mx

    try:
        from mxnet_tpu.models import transformer as tfm
        from mxnet_tpu import kernel_tier
        from mxnet_tpu.analysis import memplan
        from mxnet_tpu.telemetry.mfu import device_hbm_bytes

        V, D, L, H = 32000, 512, 8, 8
        T, B = 1024, 8
        n_batches = 8

        sym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L,
                             n_head=H, seq_len=T)
        it = tfm.SyntheticLMIter(V, B, T, n_batches=n_batches, seed=0)
        mod = mx.mod.Module(sym, context=mx.tpu())
        steps = []

        def cb(param):
            steps.append(time.perf_counter())

        mod.fit(it, num_epoch=2, optimizer="sgd",
                optimizer_params=(("learning_rate", 0.05),),
                initializer=mx.initializer.Xavier(),
                batch_end_callback=cb)
        # steady state: the second epoch's inter-batch gaps
        laps = np.diff(steps[n_batches:])
        step_s = float(np.median(laps)) if len(laps) else None
        train_tok_s = (B * T / step_s) if step_s else None

        # incremental decode tokens/s through the KV cache
        args, _ = mod.get_params()
        dec_sym = tfm.get_decode_symbol(vocab_size=V, d_model=D,
                                        n_layer=L, n_head=H, capacity=T)
        dec = mx.mod.Module(dec_sym, label_names=[], context=mx.tpu())
        dec.bind([("data", (B, 1))], None, for_training=False)
        dec.init_params(initializer=None, arg_params=args, aux_params={},
                        allow_missing=True)
        drv = tfm.KVCacheDecoder(dec, capacity=T)
        tokens = np.random.RandomState(0).randint(0, V, (B, T))
        drv.step(tokens[:, :1]).asnumpy()          # compile + warm
        drv.reset()
        n_dec = min(T, 64)
        tic = time.perf_counter()
        for t in range(n_dec):
            out = drv.step(tokens[:, t:t + 1])
        out.asnumpy()
        dec_s = time.perf_counter() - tic
        decode_tok_s = B * n_dec / dec_s if dec_s else None

        # max-context sweep: double the context until ME801 trips
        capacity = device_hbm_bytes() or (16 << 30)
        sweep, max_ctx = [], None
        ctx = T
        while ctx <= (1 << 20):
            plan = memplan.plan_symbol(
                tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L,
                               n_head=H, seq_len=ctx),
                {"data": (B, ctx), "softmax_label": (B * ctx,)},
                policy="dots")
            fits = plan["peak_bytes_per_device"] <= capacity
            sweep.append({"context": ctx,
                          "peak_gb": round(
                              plan["peak_bytes_per_device"] / 2**30, 3),
                          "fits": fits})
            if not fits:
                break
            max_ctx = ctx
            ctx *= 2

        attn_rows = [
            {k: d.get(k) for k in ("op", "variant", "reason", "xla_ms",
                                   "pallas_ms", "source", "shapes")}
            for d in kernel_tier.decisions()
            if "attention" in str(d.get("op", ""))]
        return {
            "model": {"vocab": V, "d_model": D, "layers": L, "heads": H,
                      "seq_len": T, "batch": B},
            "train_tokens_per_sec": None if train_tok_s is None
            else round(train_tok_s, 1),
            "step_ms": None if step_s is None else round(step_s * 1e3, 2),
            "decode_tokens_per_sec": None if decode_tok_s is None
            else round(decode_tok_s, 1),
            "max_context": max_ctx,
            "max_context_policy": "dots",
            "hbm_capacity_gb": round(capacity / 2**30, 1),
            "context_sweep": sweep,
            "attention_selection": attn_rows,
        }
    except Exception as e:          # the variant must never sink the run
        return {"error": f"{type(e).__name__}: {e}"}


def measure_lm_mfu_variant():
    """The ``lm_mfu`` flagship row (ISSUE 19): the transformer operating
    point reported the way the paper reports it — training tokens/s WITH
    the model-attributed MFU%, and serving decode tokens/s at slot
    counts {1, 8} for each KV-cache storage tier (f32 cache, int8
    weights, fp8 cache) — plus the decode-attention kernel-tier
    selection table, so the xla/pallas pick and its measured speedup
    ride in the same payload as the throughput they explain.

    MFU% follows the wall-clock honesty rule of the main metric: when
    the wall step is more than 10x the device-side floor (host-bound,
    not chip-bound), the percentage is withheld (None) and the achieved
    FLOP/s is recorded instead. ``compiles_since_warmup`` must
    be 0 at every decode point — the fp8 tier rides the same pinned
    rungs as float. Never sinks the run."""
    import time
    import numpy as np
    import mxnet_tpu as mx

    try:
        import statistics
        from mxnet_tpu.models import transformer as tfm
        from mxnet_tpu import kernel_tier
        from mxnet_tpu.telemetry import mfu as _mfu

        V, D, L, H = 32000, 512, 8, 8
        T, B = 1024, 8
        n_batches = 8

        row = {"model": {"vocab": V, "d_model": D, "layers": L,
                         "heads": H, "seq_len": T, "batch": B}}

        # --- train leg: tokens/s + model-attributed MFU% -------------
        sym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L,
                             n_head=H, seq_len=T)
        it = tfm.SyntheticLMIter(V, B, T, n_batches=n_batches, seed=0)
        mod = mx.mod.Module(sym, context=mx.tpu())
        steps = []

        def cb(param):
            steps.append(time.perf_counter())

        mod.fit(it, num_epoch=2, optimizer="sgd",
                optimizer_params=(("learning_rate", 0.05),),
                initializer=mx.initializer.Xavier(),
                batch_end_callback=cb)
        laps = np.diff(steps[n_batches:])      # second epoch only
        step_s = float(np.median(laps)) if len(laps) else None
        row["train_tokens_per_sec"] = round(B * T / step_s, 1) \
            if step_s else None
        row["step_ms"] = round(step_s * 1e3, 2) if step_s else None

        train_flops, mfu_pct, achieved = None, None, None
        try:
            table = _mfu.cost_table(
                sym, {"data": (B, T), "softmax_label": (B * T,)},
                train=True)
            train_flops = table["train_flops"]
            if step_s:
                achieved = train_flops / step_s
            peak, _ = _mfu.device_peaks()
            if peak and step_s:
                # same guard as the headline MFU: a wall step >10x the
                # device-side floor measures the host, not the chip —
                # withhold the percentage
                floor = train_flops / peak
                if step_s <= 10 * floor:
                    mfu_pct = round(100.0 * achieved / peak, 2)
                else:
                    row["mfu_note"] = (
                        f"step {step_s:.3f}s is "
                        f"{step_s / floor:.0f}x the device floor "
                        f"{floor:.4f}s — host-bound; MFU% withheld")
        except Exception as e:      # attribution must not sink the row
            row["mfu_error"] = f"{type(e).__name__}: {e}"
        row["train_mfu_pct"] = mfu_pct
        row["train_flops_per_step"] = train_flops
        row["achieved_flops_per_sec"] = achieved

        # --- decode leg: tokens/s per cache tier at slots {1, 8} -----
        # f32 = baseline cache; int8 = quantized weights (float cache);
        # fp8 = float weights with the fp8 KV-cache storage tier
        psym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L,
                              n_head=H, seq_len=8, include_loss=False,
                              max_seq_len=T)
        pmod = mx.mod.Module(psym, label_names=[], context=mx.tpu())
        pmod.bind([("data", (1, 8))], None, for_training=False)
        pmod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                               magnitude=2))
        args, _ = pmod.get_params()
        CAP = 256
        PROMPT, MAX_NEW = 16, 64
        tiers = (("f32", "", None), ("int8", "", "int8"),
                 ("fp8", "fp8", None))
        for tier, cache_dtype, compute_dtype in tiers:
            dsym = tfm.get_decode_symbol(
                vocab_size=V, d_model=D, n_layer=L, n_head=H,
                capacity=CAP, per_slot=True, max_seq_len=T,
                cache_dtype=cache_dtype or None)
            for slots in (1, 8):
                sched = mx.serve.serve_decoder(
                    dsym, args, name=f"mfu_{tier}_{slots}",
                    ladder=[slots], compute_dtype=compute_dtype,
                    context=mx.tpu(), start=True)
                rs = np.random.RandomState(slots)
                handles = []
                t0 = time.perf_counter()
                for _ in range(2 * slots):
                    handles.append(sched.submit(
                        rs.randint(0, V, PROMPT).tolist(),
                        max_new_tokens=MAX_NEW))
                toks = sum(len(h.result(timeout=600)) for h in handles)
                elapsed = time.perf_counter() - t0
                stats = sched.stats()
                sched.stop()
                row[f"decode_{tier}_slots{slots}_tokens_per_sec"] = \
                    round(toks / elapsed, 1) if elapsed else None
                row[f"decode_{tier}_slots{slots}"
                    "_compiles_since_warmup"] = \
                    stats["compiles_since_warmup"]
        row["decode_fp8_tokens_per_sec"] = \
            row.get("decode_fp8_slots8_tokens_per_sec")

        # --- decode-attention selection table + measured speedup -----
        attn_rows = [
            {k: d.get(k) for k in ("op", "variant", "reason", "xla_ms",
                                   "pallas_ms", "source", "shapes")}
            for d in kernel_tier.decisions()
            if "attention_decode" in str(d.get("op", ""))]
        row["decode_attention_selection"] = attn_rows
        speedups = [d["xla_ms"] / d["pallas_ms"] for d in attn_rows
                    if d.get("variant") == "pallas"
                    and d.get("xla_ms") and d.get("pallas_ms")]
        row["decode_attn_speedup"] = \
            round(statistics.median(speedups), 2) if speedups else None
        return row
    except Exception as e:          # the variant must never sink the run
        return {"error": f"{type(e).__name__}: {e}"}


def measure_decode_batch_variant():
    """The ``decode_batch`` variant row: aggregate KV-cache decode
    tokens/s through the continuous-batching decode scheduler
    (serve/decode.py) at slot counts {1, 4, 8} under open-loop
    arrivals — the serving-throughput multiplier ROADMAP 3(b) names.
    Each point runs a single-rung slot ladder so the figure isolates
    the slot count; occupancy and the zero-compile contract ride along
    (``compiles_since_warmup`` must be 0 at every point). Never sinks
    the run."""
    import time
    import numpy as np
    import mxnet_tpu as mx

    try:
        from mxnet_tpu.models import transformer as tfm

        V, D, L, H = 32000, 512, 8, 8
        CAP = 256
        PROMPT, MAX_NEW = 16, 64
        RATE = 200.0            # open-loop arrivals/s (saturating)

        sym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L,
                             n_head=H, seq_len=8, include_loss=False,
                             max_seq_len=CAP)
        mod = mx.mod.Module(sym, label_names=[], context=mx.tpu())
        mod.bind([("data", (1, 8))], None, for_training=False)
        mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                              magnitude=2))
        args, _ = mod.get_params()
        dec_sym = tfm.get_decode_symbol(
            vocab_size=V, d_model=D, n_layer=L, n_head=H, capacity=CAP,
            per_slot=True, max_seq_len=CAP)

        rows = {}
        for slots in (1, 4, 8):
            sched = mx.serve.serve_decoder(
                dec_sym, args, name=f"decb{slots}", ladder=[slots],
                context=mx.tpu(), start=True)
            rs = np.random.RandomState(slots)
            n_req = 3 * slots
            gaps = rs.exponential(1.0 / RATE, size=n_req)
            handles = []
            t0 = time.perf_counter()
            at = t0
            for i in range(n_req):
                at += gaps[i]
                dt = at - time.perf_counter()
                if dt > 0:
                    time.sleep(dt)
                handles.append(sched.submit(
                    rs.randint(0, V, PROMPT).tolist(),
                    max_new_tokens=MAX_NEW))
            toks = sum(len(h.result(timeout=600)) for h in handles)
            elapsed = time.perf_counter() - t0
            stats = sched.stats()
            sched.stop()
            rows[f"slots{slots}_tokens_per_sec"] = round(
                toks / elapsed, 1) if elapsed else None
            rows[f"slots{slots}_occupancy_mean"] = round(
                stats["tokens"] / (stats["iterations"] * slots), 3) \
                if stats["iterations"] else None
            rows[f"slots{slots}_compiles_since_warmup"] = \
                stats["compiles_since_warmup"]
        if rows.get("slots1_tokens_per_sec") and \
                rows.get("slots8_tokens_per_sec"):
            rows["speedup_8v1"] = round(
                rows["slots8_tokens_per_sec"]
                / rows["slots1_tokens_per_sec"], 2)

        # --- TTFT vs prompt length: chunked prefill against the
        # token-at-a-time path (ISSUE 18).  Long-context decode symbol
        # (capacity past the 2048-token prompt) on one slot, one
        # request in flight, so ttft is pure prefill latency.
        try:
            TCAP = 2048 + 64
            chunk = mx.serve.default_prefill_chunk()
            lsym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L,
                                  n_head=H, seq_len=8,
                                  include_loss=False, max_seq_len=TCAP)
            lmod = mx.mod.Module(lsym, label_names=[],
                                 context=mx.tpu())
            lmod.bind([("data", (1, 8))], None, for_training=False)
            np.random.seed(7)
            lmod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                                   magnitude=2))
            largs, _ = lmod.get_params()

            def lgen(s):
                return tfm.get_decode_symbol(
                    vocab_size=V, d_model=D, n_layer=L, n_head=H,
                    capacity=TCAP, per_slot=True, step_len=s,
                    max_seq_len=TCAP)

            plens = (64, 512, 2048)
            rs = np.random.RandomState(11)
            prompts = {n: rs.randint(0, V, n).tolist() for n in plens}
            curve = {str(n): {} for n in plens}
            for tag, ch in (("nochunk", 1), ("chunk", chunk)):
                sched = mx.serve.serve_decoder(
                    lgen(1), largs, name=f"decb_ttft_{tag}",
                    ladder=[1], start=True, context=mx.tpu(),
                    symbol_gen=lgen if ch > 1 else None,
                    prefill_chunk=ch)
                for n in plens:
                    h = sched.submit(prompts[n], max_new_tokens=2)
                    h.result(timeout=600)
                    curve[str(n)][f"{tag}_ms"] = round(h.ttft * 1e3, 2)
                sched.stop()
            for n in plens:
                c = curve[str(n)]
                c["speedup"] = round(c["nochunk_ms"] / c["chunk_ms"], 2)
            rows["ttft_curve"] = curve
            rows["ttft_prefill_chunk"] = chunk
            rows["ttft_2048_ms"] = curve["2048"]["chunk_ms"]
            rows["ttft_2048_speedup"] = curve["2048"]["speedup"]
        except Exception as e:      # sub-row must not sink the variant
            rows["ttft_error"] = f"{type(e).__name__}: {e}"

        # --- speculative decoding sub-row: a seeded draft/target pair
        # trained to memorise a deterministic Markov map (next token is
        # an affine function of the current one) so acceptance is high
        # by construction; the speedup is spec vs non-spec tokens/s at
        # slots 8 on the SAME trained target.  MXNET_SERVE_SPEC_DRAFT
        # picks the draft preset ("<d_model>x<n_layer>", "off" skips).
        draft_preset = os.environ.get("MXNET_SERVE_SPEC_DRAFT", "64x1")
        try:
            if draft_preset.strip().lower() in ("off", "none", "0", ""):
                rows["spec_decode"] = {"skipped":
                                       f"MXNET_SERVE_SPEC_DRAFT="
                                       f"{draft_preset}"}
            else:
                dd, dl = (int(x) for x in
                          draft_preset.lower().split("x"))
                SV, ST, SCAP = 128, 16, 64
                TD, TL, SH = 512, 6, 8
                K = mx.serve.default_spec_k()

                def _walk(start, length):
                    out, cur = [], int(start) % SV
                    for _ in range(length):
                        out.append(cur)
                        cur = (7 * cur + 11) % SV
                    return out

                def _markov_iter(B, n_batches, seed):
                    it = tfm.SyntheticLMIter(SV, B, ST, n_batches,
                                             seed)
                    rs2 = np.random.RandomState(seed)
                    for i in range(n_batches):
                        s = np.stack([
                            _walk(rs2.randint(0, SV), ST + 1)
                            for _ in range(B)]).astype(np.int32)
                        it._data[i] = mx.nd.array(s[:, :ST])
                        it._label[i] = mx.nd.array(
                            s[:, 1:].reshape(-1).astype(np.float32))
                    return it

                def _fit(d_model, n_layer, seed):
                    np.random.seed(seed)
                    m = mx.mod.Module(tfm.get_symbol(
                        vocab_size=SV, d_model=d_model,
                        n_layer=n_layer, n_head=SH, seq_len=ST,
                        include_loss=True, max_seq_len=SCAP),
                        context=mx.tpu())
                    m.fit(_markov_iter(16, 32, seed), num_epoch=6,
                          optimizer="sgd",
                          optimizer_params=(("learning_rate", 0.1),
                                            ("momentum", 0.9)),
                          initializer=mx.initializer.Xavier(
                              rnd_type="gaussian", magnitude=2))
                    a, _ = m.get_params()
                    return a

                def _spec_gen(d_model, n_layer):
                    return lambda s: tfm.get_decode_symbol(
                        vocab_size=SV, d_model=d_model,
                        n_layer=n_layer, n_head=SH, capacity=SCAP,
                        per_slot=True, step_len=s, max_seq_len=SCAP)

                targs = _fit(TD, TL, seed=21)
                dargs = _fit(dd, dl, seed=22)
                sprompts = [_walk(3 + 11 * i, 8) for i in range(8)]
                tps = {}
                acceptance = None
                for tag in ("spec", "base"):
                    tgen = _spec_gen(TD, TL)
                    sched = mx.serve.serve_decoder(
                        tgen(1), targs, name=f"decb_{tag}", ladder=[8],
                        context=mx.tpu(), start=True, symbol_gen=tgen,
                        prefill_chunk=8,
                        draft_symbol_gen=(_spec_gen(dd, dl)
                                          if tag == "spec" else None),
                        draft_params=(dargs if tag == "spec"
                                      else None),
                        spec_k=K if tag == "spec" else None)
                    hs = [sched.submit(p, max_new_tokens=32)
                          for p in sprompts]
                    t0 = time.perf_counter()
                    toks = sum(len(h.result(timeout=600)) for h in hs)
                    dt = time.perf_counter() - t0
                    st = sched.stats()
                    sched.stop()
                    tps[tag] = toks / dt if dt else None
                    if tag == "spec":
                        acceptance = st["spec"]["acceptance"]
                rows["spec_decode"] = {
                    "draft": draft_preset, "k": K,
                    "acceptance": acceptance,
                    "tokens_per_sec": round(tps["spec"], 1),
                    "base_tokens_per_sec": round(tps["base"], 1),
                    "model": {"vocab": SV, "d_model": TD, "layers": TL,
                              "heads": SH, "capacity": SCAP},
                }
                if tps.get("spec") and tps.get("base"):
                    rows["spec_speedup"] = round(
                        tps["spec"] / tps["base"], 2)
        except Exception as e:      # sub-row must not sink the variant
            rows["spec_decode"] = {"error": f"{type(e).__name__}: {e}"}

        # --- prefix-cache hit-rate point: 8 requests sharing a system
        # prefix via submit(prefix_id=); the first is the cold capture,
        # the rest join at cursor C off the stored rows.
        try:
            pr = mx.serve.serve_decoder(
                dec_sym, args, name="decb_prefix", ladder=[4],
                context=mx.tpu(), start=True, prefix_cache_mb=8)
            rsp = np.random.RandomState(5)
            shared = rsp.randint(0, V, CAP // 2).tolist()
            cold_ms, warm = None, []
            for i in range(8):
                h = pr.submit(shared + [1 + i], max_new_tokens=4,
                              prefix_id="bench-sys-prompt")
                h.result(timeout=600)
                if i == 0:
                    cold_ms = round(h.ttft * 1e3, 2)
                else:
                    warm.append(h.ttft * 1e3)
            pst = pr.stats()["prefix"]
            pr.stop()
            rows["prefix_hit_rate"] = pst["hit_rate"]
            rows["prefix"] = {
                "hits": pst["hits"], "misses": pst["misses"],
                "entries": pst["entries"], "bytes": pst["bytes"],
                "cold_ttft_ms": cold_ms,
                "warm_ttft_ms": round(float(np.mean(warm)), 2),
            }
        except Exception as e:      # sub-row must not sink the variant
            rows["prefix_error"] = f"{type(e).__name__}: {e}"

        rows.update({
            "model": {"vocab": V, "d_model": D, "layers": L, "heads": H,
                      "capacity": CAP},
            "prompt_len": PROMPT, "max_new_tokens": MAX_NEW,
            "open_loop_rate_req_s": RATE,
        })
        return rows
    except Exception as e:          # the variant must never sink the run
        return {"error": f"{type(e).__name__}: {e}"}


def measure_remat_memory_variant():
    """Residual-byte delta per remat policy at the resnet20 bench point
    (benchmarks/remat_memory.py): the roofline-side record of what
    ``MXNET_REMAT_POLICY`` frees and which batch bucket that admits.
    Never sinks the run."""
    try:
        from benchmarks.remat_memory import main as remat_lap
        return remat_lap(quiet=True)
    except Exception as e:          # the variant must never sink the run
        return {"error": f"{type(e).__name__}: {e}"}


def kernel_tier_selection_table():
    """The kernel-tier audit for the BENCH payload: per-op selection
    decisions (variant, reason, measured ms) + cache stats, so the r06
    measurement lands with the selection evidence attached."""
    try:
        from mxnet_tpu import kernel_tier
        rows = [{k: d.get(k) for k in ("op", "variant", "reason",
                                       "xla_ms", "pallas_ms", "source",
                                       "is_train")}
                for d in kernel_tier.decisions()]
        return {"mode": os.environ.get("MXNET_KERNEL_TIER", "auto"),
                "decisions": rows, "cache": kernel_tier.cache_info()}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def measure_ckpt_variant():
    """The ``ckpt`` variant row: exposed training stall per snapshot,
    async vs synchronous write, at the resnet20 bench point
    (benchmarks/checkpoint_stall.py). The acceptance gate of the
    async-checkpointing layer is exposed_ratio < 0.10. Runs on
    whatever backend the process has; never sinks the run."""
    try:
        from benchmarks.checkpoint_stall import main as ckpt_lap
        return ckpt_lap(quiet=True)
    except Exception as e:          # the variant must never sink the run
        return {"error": f"{type(e).__name__}: {e}"}


class _PairedRound:
    """Batch-granularity A/B pairing inside one fit epoch.

    ONE flax step runs (forced) inside Module.fit's batch_end_callback
    after each of our batches (forced): both sides accumulate laps over
    the same seconds, cancelling host drift to first order, while ours
    still runs the unmodified product hot loop (the callback is the
    standard Speedometer slot).
    """

    def __init__(self, flax_one_step, force_ours):
        self._flax = flax_one_step
        self._force = force_ours
        self.ours_laps = []
        self.flax_laps = []
        self._i = 0
        self._lap = None

    def start(self):
        self._lap = time.perf_counter()

    def __call__(self, param):             # batch_end_callback
        self._force(param)
        self.ours_laps.append(time.perf_counter() - self._lap)
        tic = time.perf_counter()
        self._flax(self._i)
        self._i += 1
        self.flax_laps.append(time.perf_counter() - tic)
        self._lap = time.perf_counter()


def main():
    import statistics

    import jax
    from mxnet_tpu.telemetry import mfu as _mfu

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py: no TPU — jax.devices() found "
                 f"{[d.platform for d in jax.devices()]}; there is no "
                 "CPU fallback")
    # one peaks table; a device it does not know is an error
    peak = _mfu.PEAKS[dev.device_kind]["bf16"]
    rng = np.random.RandomState(0)
    imgs, labels = _synthetic(rng)

    flax_one_step, flax_flops, flax_steps = setup_flax(imgs, labels)
    (mod, it, exe, force_ours, opt_params), ours_flops = \
        setup_ours(imgs, labels)

    # per-LAP pairing: each batch yields one (ours_dt, flax_dt) pair
    # sampled within the same seconds; medians over all laps are robust
    # to host latency spikes, which poison any sum- or epoch-level
    # statistic
    import gc
    ours_laps, flax_laps = [], []
    for r in range(ROUNDS):
        it.reset()
        pr = _PairedRound(flax_one_step, force_ours)
        # a GC pause lands in whichever lap is running when it fires —
        # asymmetric noise (ours' lap has more Python allocation than the
        # flax closure); collect between rounds, never inside one
        gc.collect()
        gc.disable()
        pr.start()
        try:
            mod.fit(it, num_epoch=1, optimizer_params=opt_params,
                    batch_end_callback=pr)
        finally:
            # an exception mid-round must not leave GC off for the rest
            # of the process (ADVICE r5)
            gc.enable()
        # drop each round's first lap from BOTH sides: it carries fit's
        # epoch prologue (iterator/metric reset, re-bind guards), which
        # the flax closure has no analog of — steady-state throughput is
        # the comparison; the exclusion count is recorded in the JSON
        pr.ours_laps = pr.ours_laps[1:]
        pr.flax_laps = pr.flax_laps[1:]
        o = BATCH / statistics.median(pr.ours_laps)
        f = BATCH / statistics.median(pr.flax_laps)
        _log(f"round {r}: ours {o:.1f} img/s, flax {f:.1f} img/s "
             f"(median lap), ratio {o / f:.2f}")
        ours_laps.extend(pr.ours_laps)
        flax_laps.extend(pr.flax_laps)
    lap_ratios = sorted(f / o for o, f in zip(ours_laps, flax_laps))
    ratio = statistics.median(lap_ratios)
    ours_img_s = BATCH / statistics.median(ours_laps)
    flax_img_s = BATCH / statistics.median(flax_laps)
    ratios = lap_ratios          # reported per-lap, sorted

    def _lap_summary(laps):
        s = sorted(laps)
        pick = lambda q: s[min(len(s) - 1, int(q * len(s)))]
        return {"p10": round(pick(0.10), 3), "p50": round(pick(0.50), 3),
                "p90": round(pick(0.90), 3), "n": len(s)}

    # methodology self-check (frozen r04 paired-lap method): each lap is
    # exactly one ours fused batch (fit's batch_end_callback fires once
    # per batch) followed by exactly one forced flax step; the counters
    # prove both sides submitted the same number of device steps
    steps_ours = len(ours_laps)
    steps_flax = flax_steps[0]              # one_step calls = timed laps
    paired_ok = (steps_ours == len(lap_ratios)
                 == ROUNDS * (N_BATCHES - 1)
                 and steps_flax == ROUNDS * N_BATCHES)

    # spmd variant (after the paired laps, so its compiles never
    # contend with the measured rounds): the GSPMD path vs the
    # kvstore-overlap path on this host's mesh
    _log("spmd variant (spmd_vs_kvstore paired lap)")
    spmd_variant = measure_spmd_variant()

    # serve variant (also post-laps): req/s at a p99 SLO through the
    # continuous-batching server — the second bench axis (ROADMAP 3)
    _log("serve variant (Poisson open-loop vs p99 SLO)")
    serve_variant = measure_serve_variant()

    # quant variant: the same serve protocol, int8 ladder vs float —
    # the low-precision tier's capacity multiplier (ROADMAP 4)
    _log("quant variant (int8 vs float serve ladder)")
    quant_variant = measure_quant_serve_variant()

    # ckpt variant: async-vs-sync exposed snapshot stall (ROADMAP 5)
    _log("ckpt variant (checkpoint_stall paired lap)")
    ckpt_variant = measure_ckpt_variant()

    # remat variant: per-policy residual bytes + admitted batch bucket
    _log("remat variant (residual bytes per policy)")
    remat_variant = measure_remat_memory_variant()

    # lm variant: transformer tokens/s + KV-decode + max-context sweep
    # (ROADMAP 1) — the attention xla/flash/ring selection table rides in
    _log("lm variant (transformer train/decode/max-context)")
    lm_variant = measure_lm_variant()

    # lm_mfu flagship variant: train MFU% + per-cache-tier decode
    # tokens/s + the decode-attention selection table (ISSUE 19)
    _log("lm_mfu variant (transformer MFU flagship)")
    lm_mfu_variant = measure_lm_mfu_variant()

    # decode_batch variant: continuous-batching aggregate decode
    # tokens/s at slots {1, 4, 8} (ROADMAP 3b)
    _log("decode_batch variant (slot-pooled continuous batching)")
    decode_batch_variant = measure_decode_batch_variant()

    # per-op MFU attribution + roofline from the registry cost metadata
    # (telemetry/mfu.py): coverage is attributed FLOPs over the XLA
    # compiled-program count — the honesty check on the per-op numbers
    from mxnet_tpu.ops.cost import optimizer_flops as _opt_flops
    roofline_rows, mfu_coverage, attributed_flops = None, None, None
    try:
        table = _mfu.cost_table(
            mod._symbol, {"data": (BATCH, 3, 224, 224),
                          "softmax_label": (BATCH,)}, train=True)
        n_params = sum(int(np.prod(a.shape))
                       for a in (mod._arg_params or {}).values())
        attributed_flops = table["train_flops"] + \
            _opt_flops("sgd_mom", n_params)
        if ours_flops:
            mfu_coverage = round(attributed_flops / ours_flops, 3)
        peak_flops, peak_bw = _mfu.device_peaks(dev.device_kind)
        roofline_rows = [
            {"op": r["op"], "share": round(r["share"], 3),
             "ai": round(r["ai"], 1), "bound": r["bound"],
             "attainable_frac": round(r.get("attainable_frac", 0), 3)}
            for r in _mfu.roofline(table, peak_flops, peak_bw,
                                   train=True, top=8)]
    except Exception as e:
        _log(f"mfu attribution unavailable: {e!r}")

    # MFU from wall-clock is only a measurement when the wall clock is
    # actually dominated by device compute. When the step time is >10x
    # the device-side floor (flops/peak), publishing
    # flops/(peak*step_time) would present host latency as a
    # chip-utilization figure. Null it instead, with the floor ratio
    # recorded so the reader can see why.
    mfu_note = None

    def mfu(img_s, flops):
        nonlocal mfu_note
        if not (peak and flops):
            return None
        step_time = BATCH / img_s
        device_floor = flops / peak
        if step_time > 10 * device_floor:
            mfu_note = (f"wall step time {step_time:.2f}s is "
                        f"{step_time / device_floor:.0f}x the device-side "
                        f"floor {device_floor:.3f}s — host-bound; "
                        "wall-clock MFU withheld")
            return None
        return round(flops / (peak * step_time), 4)

    print(json.dumps({
        "metric": "resnet50_bf16_b256_train_img_per_sec_vs_flax_1chip",
        "value": round(ours_img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(ratio, 3),
        "flax_ref_img_s": round(flax_img_s, 2),
        "ratio_vs_flax": round(ratio, 3),
        "lap_ratios_sorted": [round(r, 3) for r in ratios],
        "n_paired_laps": len(ratios),
        "lap_ratio_p10": round(ratios[int(0.10 * len(ratios))], 3),
        "ours_lap_seconds": _lap_summary(ours_laps),
        "flax_lap_seconds": _lap_summary(flax_laps),
        "paired_step_check": {"ours_timed_laps": steps_ours,
                              "flax_device_steps": steps_flax,
                              "warmup_laps_excluded_per_round": 1,
                              "consistent": paired_ok},
        "spmd": spmd_variant,
        "serve": serve_variant,
        "quant": quant_variant,
        "ckpt": ckpt_variant,
        "remat_memory": remat_variant,
        "lm": lm_variant,
        "lm_mfu": lm_mfu_variant,
        "decode_batch": decode_batch_variant,
        "kernel_tier_selection": kernel_tier_selection_table(),
        "mfu_ours": mfu(ours_img_s, ours_flops),
        "mfu_flax": mfu(flax_img_s, flax_flops),
        "mfu_model_attributed": mfu(ours_img_s, attributed_flops),
        "mfu_coverage": mfu_coverage,
        "roofline": roofline_rows,
        "kernel_tier": os.environ.get("MXNET_KERNEL_TIER", "auto"),
        "mfu_note": mfu_note,
        "flops_per_step_ours": ours_flops,
        "flops_per_step_flax": flax_flops,
        "device": dev.device_kind,
        "vs_p100_context": round(ours_img_s / REFERENCE_P100_IMG_S, 1),
    }))


if __name__ == "__main__":
    main()
