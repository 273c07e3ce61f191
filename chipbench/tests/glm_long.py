#!/usr/bin/env python3
"""By hand, on the chip: the GLM-5.2 configuration's served path against
the plain reference over 8,364 positions of one sequence, at the
published widths - what ``check_reference``'s 4,112 positions do not
reach. The top rung's window program prefills to 8,300 in ragged
dispatches (300, then 1,024 seven times, then 832: ``index_topk`` 2,048
is passed inside the third dispatch, and from there every query drops
keys, three quarters of them at the end), then its S = 1 program decodes
to 8,364. Every fed position's logits against ``archs/glm_dsa.py``'s
reference under its ``LOGIT_TOL``, and the same for the two controls,
each of which has to fail: every matmul operand in float8, and the
reference without the selection. Prints one JSON line.

    python3 chipbench/tests/glm_long.py [--seed N] [--rehearse]

``--rehearse`` runs the tiny fixture on the CPU (tests/fixtures/glm_dsa:
``index_topk`` 16, the same schedule scaled down); no number of it is a
device number, and at that size a key swapped by bfloat16's rounding is
a sixteenth of a query's attention, so its ``ok`` decides nothing."""
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
    path = os.path.join(HERE, "fixtures", "glm_dsa", "configs",
                        "tiny-glm.json") if ns.rehearse else \
        os.path.join(ROOT, "chipbench", "configs", "glm-5.2.json")
    with open(path) as f:
        cfg = json.load(f)
    os.environ.update(cfg.get("env", {}))
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from chipbench import serve_runner
    from chipbench.reference import glm_dsa as reference
    arch = manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", "glm_dsa.py"))

    S, topk = cfg["prefill_chunk"], cfg["index_topk"]
    first, tail, n_decode = (300, 832, 64) if not ns.rehearse else (5, 11, 8)
    n_full = 7 if not ns.rehearse else 4
    t_pre = first + n_full * S + tail           # 8,300 (80)
    total = t_pre + n_decode                    # 8,364 (88)
    assert total >= 4 * topk

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
    seqs = rng.integers(0, cfg["vocab_size"], (1, total)).astype(np.int32)
    got = np.zeros((1, total, cfg["vocab_size"]), np.float32)
    drv.join(0)
    at = 0
    for n in [first] + [S] * n_full + [tail]:
        tokens = np.zeros((top, S), np.int32)
        tokens[:1, :n] = seqs[:, at:at + n]
        fed = np.zeros(top, np.int32)
        fed[:1] = n
        out = drv.step(tokens, fed=fed).asnumpy()
        got[:, at:at + n] = out[:1, :n]
        at += n
    for _ in range(n_decode):
        tokens = np.zeros((top, 1), np.int32)
        tokens[:1, 0] = seqs[:, at]
        fed = np.zeros(top, np.int32)
        fed[:1] = 1
        got[:, at] = drv.step(tokens, fed=fed).asnumpy()[:1, 0]
        at += 1
    assert int(drv.pos[0]) == total
    served_s = time.perf_counter() - t0 - setup_s

    params = serve_runner.served_params(engine)
    rcfg = dict(cfg, indexer_types=cfg["indexer_types_run"])
    tol = arch.LOGIT_TOL

    def against(want, other):
        err = np.abs(np.asarray(other) - want)
        bound = tol + tol * np.abs(want)
        return {"max_abs_err": float(err.max()),
                "max_err_over_bound": float((err / bound).max())}

    def ref(**kw):
        fwd = jax.jit(functools.partial(reference.forward, config=rcfg,
                                        **kw))
        return np.asarray(fwd(params, seqs))

    want = ref()
    report = {"served": against(want, got)}
    parts = {"window_program_before_index_topk": slice(0, topk),
             "window_program_selecting": slice(topk, t_pre),
             "decode_program": slice(t_pre, total)}
    for name, where in parts.items():
        report["served_" + name] = against(want[:, where], got[:, where])
    late = slice(topk, total)       # where a selection drops keys
    report["bfloat16_emulation"] = against(want, ref(round_to=jnp.bfloat16))
    report["float8_control"] = against(
        want[:, late], ref(round_to=jnp.float8_e4m3fn)[:, late])
    report["no_selection_control"] = against(
        want[:, late], ref(select=False)[:, late])
    ok = report["served"]["max_err_over_bound"] <= 1.0
    controls_fail = all(report[c]["max_err_over_bound"] > 1.0
                        for c in ("float8_control", "no_selection_control"))
    device = jax.devices()[0]
    print(json.dumps({
        "glm_long": cfg["name"], "seed": ns.seed, "positions": total,
        "sequences": 1, "prefilled": t_pre, "index_topk": topk,
        "tolerance": tol, "max_abs_logit": float(np.abs(want).max()),
        "ok": bool(ok), "controls_fail": bool(controls_fail), **report,
        "setup_s": setup_s, "served_s": served_s,
        "platform": device.platform, "device_kind": device.device_kind,
        "rehearsal": ns.rehearse}), flush=True)
    return 0 if ok and controls_fail else 1


if __name__ == "__main__":
    sys.exit(main())
