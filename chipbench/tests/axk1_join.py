#!/usr/bin/env python3
"""By hand, on the chip: a request joined from the prefix store and a
cold prefill of the same prompt give the same logits, at the A.X-K1
configuration's published widths. One prompt of a 16,384-token document
and 300 tokens of its own: slot 0 prefills it cold through the top
rung's window program (17 dispatches); its first 16,384 rows are
captured as the store would hold them (host numpy), restored into slot
1, the cursor set to 16,384, and the 300 tokens fed there in one
dispatch. The logits at the first answered position (the prompt's last
token) of both, their difference, and whether slot 1's latent rows equal
slot 0's bitwise. Then the same through the scheduler: the prompt
submitted twice under one ``prefix_id`` - a miss that captures, a hit
that joins - answers the same tokens. Prints one JSON line.

    python3 chipbench/tests/axk1_join.py [--seed N] [--rehearse]

``--rehearse`` runs the tiny fixture on the CPU (tests/fixtures/axk1: a
document of 48 tokens and 5 of the prompt's own); no number of it is a
device number."""
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
    ap.add_argument("--seed", type=int, default=2147480177)
    ap.add_argument("--rehearse", action="store_true")
    ns = ap.parse_args(argv)
    from chipbench import common, manifest
    common.set_caches()
    path = os.path.join(HERE, "fixtures", "axk1", "configs",
                        "tiny-axk1.json") if ns.rehearse else \
        os.path.join(ROOT, "chipbench", "configs", "a.x-k1.json")
    with open(path) as f:
        cfg = json.load(f)
    os.environ.update(cfg.get("env", {}))
    import jax
    import numpy as np
    import mxnet_tpu as mx
    arch = manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", "axk1.py"))

    S = cfg["prefill_chunk"]
    doc, own = (16384, 300) if not ns.rehearse else (48, 5)
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

    rng = np.random.default_rng([ns.seed % (1 << 32), 17])
    prompt = rng.integers(0, cfg["vocab_size"], doc + own).astype(np.int32)

    def feed(slot, start):
        """The prompt from ``start`` on through slot ``slot``: the
        logits of its last token."""
        at, out = start, None
        while at < len(prompt):
            n = min(S, len(prompt) - at)
            tokens = np.zeros((top, S), np.int32)
            tokens[slot, :n] = prompt[at:at + n]
            fed = np.zeros(top, np.int32)
            fed[slot] = n
            out = drv.step(tokens, fed=fed).asnumpy()
            # a packed window hands back each slot's last fed row alone
            out = out[slot, 0 if out.shape[1] == 1 else n - 1]
            at += n
        return np.asarray(out, np.float32)

    drv.join(0)
    t = time.perf_counter()
    cold = feed(0, 0)
    cold_s = time.perf_counter() - t
    t = time.perf_counter()
    rows = drv.capture_rows(0, doc)
    capture_s = time.perf_counter() - t
    drv.join(1)
    t = time.perf_counter()
    put = drv.restore_rows(1, rows)
    drv.rewind(1, doc)
    jax.block_until_ready([c.asjax() for _n, c in drv._kv_cells()])
    restore_s = time.perf_counter() - t
    joined = feed(1, doc)
    pools_equal = all(
        np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(drv.capture_rows(0, len(prompt)).values(),
                        drv.capture_rows(1, len(prompt)).values()))
    drv.leave(0)
    drv.leave(1)
    drv.rewind_many([0, 1], [0, 0])

    handles = []
    for _ in range(2):
        h = sched.submit(prompt, max_new_tokens=8, prefix_id="doc")
        sched.pump()
        handles.append(list(h.result(timeout=600)))
    stats = sched.stats()["prefix"]
    device = jax.devices()[0]
    diff = float(np.max(np.abs(cold - joined)))
    ok = diff == 0.0 and pools_equal and handles[0] == handles[1] \
        and stats["hits"] == 1
    print(json.dumps({
        "axk1_join": cfg["name"], "seed": ns.seed, "document": doc,
        "prompt": len(prompt), "max_abs_logit": float(np.abs(cold).max()),
        "max_abs_diff_joined_vs_cold": diff,
        "argmax_equal": bool(cold.argmax() == joined.argmax()),
        "latent_pools_bitwise_equal": bool(pools_equal),
        "scheduler_answers_equal": handles[0] == handles[1],
        "store": stats, "bytes_put": int(put), "capture_s": capture_s,
        "restore_s": restore_s, "cold_prefill_s": cold_s,
        "setup_s": setup_s, "ok": bool(ok), "platform": device.platform,
        "device_kind": device.device_kind, "rehearsal": ns.rehearse}),
        flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
