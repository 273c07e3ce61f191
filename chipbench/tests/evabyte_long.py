#!/usr/bin/env python3
"""By hand, on the chip: the EvaByte configuration's served path against
the plain reference over 4,200 positions of two sequences, at the
published widths - what ``check_reference``'s 2,064 positions do not
reach. The top rung's window program prefills to 4,000 in ragged
dispatches (300, then 512 a time, then 116: the boundary at 2,048 falls
inside a dispatch, and the windows from 2,048 on read summaries), then
its S = 1 program decodes to 4,200 (the window at 4,096 closes during
decode). Every fed position's head-0 logits against
``archs/evabyte.py``'s reference under its ``LOGIT_TOL``, and the same
for the controls, which have to fail. Prints one JSON line.

    python3 chipbench/tests/evabyte_long.py [--seed N] [--rehearse]

``--rehearse`` runs the tiny fixture on the CPU (tests/fixtures/evabyte:
window 32, the same schedule scaled down); no number of it is a device
number."""
import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147480077)
    ap.add_argument("--rehearse", action="store_true")
    ns = ap.parse_args(argv)
    from chipbench import common, manifest
    common.set_caches()
    path = os.path.join(HERE, "fixtures", "evabyte", "configs",
                        "tiny-evabyte.json") if ns.rehearse else \
        os.path.join(ROOT, "chipbench", "configs", "evabyte-6.5b.json")
    with open(path) as f:
        cfg = json.load(f)
    os.environ.update(cfg.get("env", {}))
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from chipbench import serve_runner
    from chipbench.reference import evabyte as reference
    arch = manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", "evabyte.py"))

    W, S = cfg["window_size"], cfg["prefill_chunk"]
    first, tail, n_decode = (300, 116, 200) if not ns.rehearse else (9, 3, 12)
    n_full = 7 if not ns.rehearse else 3
    t_pre = first + n_full * S + tail           # 4,000 (60)
    total = t_pre + n_decode                    # 4,200 (72)
    assert t_pre < (t_pre // W + 1) * W < total    # decode closes a window

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
    engine = sched.engine
    drv = engine.driver(top)
    setup_s = time.perf_counter() - t0

    rng = np.random.default_rng([ns.seed % (1 << 32), 13])
    seqs = rng.integers(0, cfg["vocab_size"], (2, total)).astype(np.int32)
    got = np.zeros((2, total, cfg["vocab_size"]), np.float32)
    drv.join(0), drv.join(1)
    at = 0
    for n in [first] + [S] * n_full + [tail]:
        tokens = np.zeros((top, S), np.int32)
        tokens[:2, :n] = seqs[:, at:at + n]
        fed = np.zeros(top, np.int32)
        fed[:2] = n
        out = drv.step(tokens, fed=fed).asnumpy()
        got[:, at:at + n] = out[:2, :n]
        at += n
    for _ in range(n_decode):
        tokens = np.zeros((top, 1), np.int32)
        tokens[:2, 0] = seqs[:, at]
        fed = np.zeros(top, np.int32)
        fed[:2] = 1
        got[:, at] = drv.step(tokens, fed=fed).asnumpy()[:2, 0]
        at += 1
    assert list(drv.pos[:2]) == [total, total]
    served_s = time.perf_counter() - t0 - setup_s

    params = serve_runner.served_params(engine)
    tol = arch.LOGIT_TOL

    def against(want, other):
        err = np.abs(np.asarray(other) - want)
        bound = tol + tol * np.abs(want)
        return {"max_abs_err": float(err.max()),
                "max_err_over_bound": float((err / bound).max())}

    def ref(**kw):
        fwd = jax.jit(functools.partial(reference.forward, config=cfg, **kw))
        return np.asarray(fwd(params, seqs))

    want = ref()
    report = {"served": against(want, got)}
    parts = {"window_program_before_2048": slice(0, W),
             "window_program_reading_summaries": slice(W, t_pre),
             "decode_program": slice(t_pre, total)}
    for name, where in parts.items():
        report["served_" + name] = against(want[:, where], got[:, where])
    report["bfloat16_emulation"] = against(want, ref(round_to=jnp.bfloat16))
    report["state_float8_control"] = against(
        want, ref(state_to=jnp.float8_e4m3fn))
    report["float8_control"] = against(want, ref(round_to=jnp.float8_e4m3fn))
    ok = report["served"]["max_err_over_bound"] <= 1.0
    control_fails = report["float8_control"]["max_err_over_bound"] > 1.0
    device = jax.devices()[0]
    print(json.dumps({
        "evabyte_long": cfg["name"], "seed": ns.seed, "positions": total,
        "sequences": 2, "prefilled": t_pre, "window": W,
        "tolerance": tol, "max_abs_logit": float(np.abs(want).max()),
        "ok": bool(ok), "control_fails": bool(control_fails), **report,
        "setup_s": setup_s, "served_s": served_s,
        "platform": device.platform, "device_kind": device.device_kind,
        "rehearsal": ns.rehearse}), flush=True)
    return 0 if ok and control_fails else 1


if __name__ == "__main__":
    sys.exit(main())
