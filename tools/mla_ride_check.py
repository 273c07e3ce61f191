#!/usr/bin/env python3
"""By hand, on the chip: a slot that a prefill window feeds ONE row, at a
long context, at a latent-attention configuration's published widths.

``chipbench/serve_runner.py::check_reference`` feeds its sequences whole
windows and then steps at S = 1, so the comparison that decides
``correct`` never feeds a slot one row inside a window - the case
``mla_attention_decode`` attends in its S = 1 form (``mla_attn_ride``,
``ops/mla.py``). Here slot 0 prefills a sequence to 16,384 positions
through the top rung's window program; then, sixteen times, a window
feeds slot 0 its next token alone while slot 1 prefills 1,024 tokens of
another sequence (slot 0 rides); then slot 0 is rewound to 16,384 and
fed the same sixteen tokens through the S = 1 program. The riding rows'
logits against the S = 1 dispatch's at the same cursors, both against
the architecture's plain float32 reference under its ``LOGIT_TOL``, and
whether the sixteen latent rows each path wrote are equal. Prints one
JSON line.

    python3 tools/mla_ride_check.py --config a.x-k1|glm-5.2 [--seed N]
                                    [--rehearse]

``--rehearse`` runs the configuration's tiny fixture on the CPU
(chipbench/tests/fixtures: a context of 64, windows of 16); no number of
it is a device number."""
import argparse
import functools
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_TINY = {"a.x-k1": ("axk1", "tiny-axk1.json"),
         "glm-5.2": ("glm_dsa", "tiny-glm.json")}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=sorted(_TINY), required=True)
    ap.add_argument("--seed", type=int, default=2147480241)
    ap.add_argument("--rehearse", action="store_true")
    ns = ap.parse_args(argv)
    from chipbench import common, manifest
    common.set_caches()
    fixture, tiny = _TINY[ns.config]
    path = os.path.join(ROOT, "chipbench", "tests", "fixtures", fixture,
                        "configs", tiny) if ns.rehearse else \
        os.path.join(ROOT, "chipbench", "configs", ns.config + ".json")
    with open(path) as f:
        cfg = json.load(f)
    os.environ.update(cfg.get("env", {}))
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from chipbench import serve_runner
    arch = manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", cfg["arch"] + ".py"))

    S, n_cmp = cfg["prefill_chunk"], 16
    ctx = 16384 if not ns.rehearse else 4 * S
    assert ctx % S == 0 and ctx + n_cmp + S <= cfg["capacity"]
    gen = functools.partial(arch.decode_symbol, cfg)
    top = max(cfg["ladder"])
    t0 = time.perf_counter()
    args = arch.make_params(gen(1), arch.data_shapes(cfg, top, 1), ns.seed,
                            cfg)
    sched = mx.serve.serve_decoder(
        gen(1), args, name=cfg["name"], capacity=cfg["capacity"],
        ladder=[top], context=mx.cpu(0) if ns.rehearse else mx.tpu(0),
        compute_dtype=cfg["compute_dtype"], symbol_gen=gen,
        prefill_chunk=S, start=False)
    del args
    drv = sched.engine.driver(top)
    setup_s = time.perf_counter() - t0

    rng = np.random.default_rng([ns.seed % (1 << 32), 41])
    seq = rng.integers(0, cfg["vocab_size"], (1, ctx + n_cmp)) \
        .astype(np.int32)
    other = rng.integers(0, cfg["vocab_size"], n_cmp * S).astype(np.int32)

    def dispatch(width, rows):
        """One dispatch of ``width`` rows a slot; ``rows`` maps a slot to
        the tokens it is fed. Returns the logits, on the host."""
        tokens = np.zeros((top, width), np.int32)
        fed = np.zeros(top, np.int32)
        for slot, toks in rows.items():
            tokens[slot, :len(toks)] = toks
            fed[slot] = len(toks)
        return drv.step(tokens, fed=fed).asnumpy()

    def written(slot):
        """The rows past ``ctx`` of every positional pool of ``slot``."""
        return [np.asarray(a, np.float32)[..., ctx:, :]
                for a in drv.capture_rows(slot, ctx + n_cmp).values()]

    drv.join(0)
    drv.join(1)
    for w in range(ctx // S):
        dispatch(S, {0: seq[0, w * S:(w + 1) * S]})
    ride = np.zeros((n_cmp, cfg["vocab_size"]), np.float32)
    for j in range(n_cmp):
        if int(drv.pos[1]) + S > cfg["capacity"]:   # the tiny fixture's
            drv.rewind(1, 0)
        ride[j] = dispatch(S, {0: seq[0, ctx + j:ctx + j + 1],
                               1: other[j * S:(j + 1) * S]})[0, 0]
    assert int(drv.pos[0]) == ctx + n_cmp
    ride_rows = written(0)
    drv.leave(1)
    drv.rewind_many([0, 1], [ctx, 0])
    step = np.zeros_like(ride)
    for j in range(n_cmp):
        step[j] = dispatch(1, {0: seq[0, ctx + j:ctx + j + 1]})[0, 0]
    rows_equal = all(np.array_equal(a, b)
                     for a, b in zip(ride_rows, written(0)))

    # the reference beside the parameters alone: the engine's pools and
    # programs go first, 16 k positions in float32 do not fit beside them
    params = serve_runner.served_params(sched.engine)
    drv = None
    sched = None
    gc.collect()
    rcfg = arch._reference_cfg(cfg) if hasattr(arch, "_reference_cfg") \
        else cfg
    tol = arch.LOGIT_TOL

    def against(want, got):
        err = np.abs(got - want)
        bound = tol + tol * np.abs(want)
        return {"max_abs_err": float(err.max()),
                "max_err_over_bound": float((err / bound).max())}

    report = {}
    try:
        fwd = jax.jit(functools.partial(arch._reference.forward, config=rcfg,
                                        tail=n_cmp))
        want = np.asarray(fwd(params, seq))[0]
        report = {"riding_vs_reference": against(want, ride),
                  "s1_vs_reference": against(want, step),
                  "max_abs_logit": float(np.abs(want).max())}
        ok_ref = report["riding_vs_reference"]["max_err_over_bound"] <= 1.0
    except jax.errors.JaxRuntimeError as e:     # the device's memory
        report = {"reference": f"failed: {type(e).__name__}: "
                               f"{str(e)[:300]}"}
        ok_ref = False
    diff = np.abs(ride - step)
    device = jax.devices()[0]
    print(json.dumps({
        "mla_ride_check": cfg["name"], "seed": ns.seed, "context": ctx,
        "positions_compared": n_cmp, "window": S, "slots": top,
        "riding_vs_s1_max_abs_diff": float(diff.max()),
        "riding_vs_s1_rows_bitwise_equal": int(
            (diff.max(axis=1) == 0.0).sum()),
        "riding_vs_s1_argmax_equal": int(
            (ride.argmax(1) == step.argmax(1)).sum()),
        "latent_rows_written_equal": bool(rows_equal), "tolerance": tol,
        **report, "setup_s": setup_s, "ok": bool(ok_ref),
        "platform": device.platform, "device_kind": device.device_kind,
        "rehearsal": ns.rehearse}), flush=True)
    return 0 if ok_ref else 1


if __name__ == "__main__":
    sys.exit(main())
