#!/usr/bin/env python3
"""By hand, on the chip: ``eva_attention_decode`` alone at EvaByte's
published sizes (8 slots, 32 heads of 128, a window of 2,048, chunks of
16, 2,048 summaries, bfloat16), a window of 512 rows whose slots are fed
a whole chunk, a ragged one, ONE row (riding) and nothing.

Which launches a slot takes in a program of S > 1 is read from ``fed``
(``ops/eva.py``): the window's kernels, the S = 1 geometry again
(``eva_*_ride``) or neither. The benchmark's ``check_reference`` feeds
one chunk and one rider; here eight slots stand at cursors chosen for
the edges - inside a window, on a window's last row (a rider there
closes a chunk AND a window), on a ring block's first row, at 0 - and
one dispatch feeds them by a case of ``_FED``. Both lowerings run the same
schedule from an empty state. Compared: the fed rows of the window's
output, Pallas against the XLA composition (bfloat16 operands on both
sides); the state it left - the rings bit for bit (a row is a copy),
the summaries within a bfloat16 rounding, the cursors; the four pools
of every slot fed nothing, bit for bit what they were; and the riders'
row and state against the S = 1 program fed the same row at the same
cursors, bit for bit (the same kernels at the same geometry). Then the
window program's time on the host's clock, state donated, by what it is
fed (every case of ``_FED``, the median of ``--times`` launches), and
with ``--trace`` each case's device time by operation from the
profiler's trace. Prints one JSON line.

    python3 tools/eva_ride_check.py [--seed N] [--repo DIR] [--times N]
                                    [--trace] [--rehearse]

``--repo DIR`` imports the program from another checkout (a parent's:
it has no riding form, so only the times are to be compared).
``--rehearse`` runs six slots of tiny sizes on the CPU, interpreted, in
float32; no number of it is a device number."""
import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cursors(W):
    """Eight slots' cursors (a rehearsal takes the first six): inside
    the second window, on a window's last row, on a ring block's first
    row, at 0, inside the second window past a block's edge, on the
    third window's last row, early in the first, inside the third."""
    return [W + W // 6, W - 1, W // 2, 0, W + W // 2 + 8, 3 * W - 1,
            W // 16, 2 * W + W // 4]


#: the rows each slot is fed, of a dispatch of S, by case
_FED = {"mixed": lambda S: [S, 1, 1, 0, S // 3, 1, 0, 1],
        "chunk_and_riders": lambda S: [S, 1, 1, 1, 1, 1, 1, 1],
        "chunk_alone": lambda S: [S, 0, 0, 0, 0, 0, 0, 0],
        "all_riding": lambda S: [1] * 8,
        "all_dead": lambda S: [0] * 8,
        "whole_windows": lambda S: [S] * 8}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147480062)
    ap.add_argument("--repo", default=ROOT)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--times", type=int, default=30)
    ap.add_argument("--trace", action="store_true")
    ns = ap.parse_args(argv)
    sys.path.insert(0, ns.repo)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu  # noqa: F401 - the compile cache, the ops
    from mxnet_tpu.ops.registry import get_op

    if ns.rehearse:
        B, H, d, W, C, cap, S, dtype = 6, 2, 16, 256, 16, 1024, 32, jnp.float32
    else:
        B, H, d, W, C, cap, S, dtype = 8, 32, 128, 2048, 16, 32768, 512, \
            jnp.bfloat16
    cursors = np.asarray(_cursors(W)[:B])
    opdef = get_op("eva_attention_decode")
    attrs = opdef.normalize_attrs(
        {"capacity": cap, "window": W, "chunk": C, "rope_base": 1e5})
    key = jax.random.PRNGKey(ns.seed % (2 ** 31))
    kq, kk, kv, kp, km = jax.random.split(key, 5)
    T = int(cursors.max()) + S + 8
    q, k, v = (jax.random.normal(x, (B, H, T, d), jnp.float32).astype(dtype)
               for x in (kq, kk, kv))
    phi, mu = (jax.random.normal(x, (H, d), jnp.float32).astype(dtype)
               for x in (kp, km))

    def rows(at, n, fed):
        """The op's inputs: slot b's rows from ``at[b]``, ``n`` of them,
        junk past ``fed[b]``."""
        idx = jnp.asarray(at)[:, None] + jnp.arange(n)[None, :]
        real = (jnp.arange(n)[None, :] < jnp.asarray(fed)[:, None])[
            :, None, :, None]
        take = jax.vmap(lambda x, i: x[:, i])
        return [jnp.where(real, take(x, idx), junk).astype(dtype)
                for x, junk in ((q, 7.0), (k, -9.0), (v, 5.0))] \
            + [jnp.asarray(fed, jnp.int32), phi, mu]

    def program(variant):
        fn = opdef.variant_fn(variant)
        return jax.jit(lambda ins, aux: fn(attrs, ins, aux, False, None))

    def empty():
        return [jnp.zeros((B, H, W, d), dtype) for _ in range(2)] \
            + [jnp.zeros((B, H, cap // C, d), dtype) for _ in range(2)] \
            + [jnp.zeros((B, 1), jnp.int32)]

    def filled(run):
        aux, at = empty(), np.zeros(B, int)
        while (at < cursors).any():
            fed = np.minimum(S, cursors - at)
            _, aux = run(rows(at, S, fed), aux)
            at += fed
        return aux

    def host(arrays):
        return [np.asarray(a.astype(jnp.float32)) for a in arrays]

    report = {"tool": "eva_ride_check", "repo": ns.repo, "seed": ns.seed,
              "device": jax.devices()[0].device_kind,
              "slots": B, "S": S, "window": W, "cursors": cursors.tolist()}
    rides = "_NAMES" in vars(sys.modules["mxnet_tpu.ops.eva"])
    report["riding_form"] = rides
    window = {name: program(name) for name in ("pallas", "xla")}
    if rides:
        before = {name: filled(run) for name, run in window.items()}
        for case in ("mixed", "chunk_and_riders", "all_riding", "all_dead"):
            fed = _FED[case](S)[:B]
            ins = rows(cursors, S, fed)
            got = {name: run(ins, before[name])
                   for name, run in window.items()}
            (out,), new = got["pallas"]
            (plain,), same = got["xla"]
            out, plain = host([out, plain])
            real = np.arange(S)[None, :] < np.asarray(fed)[:, None]
            err = np.abs(out - plain).max(axis=(1, 3))[real]
            old, new, same = (host(x) for x in (before["pallas"], new, same))
            dead = np.asarray(fed) == 0
            riding = np.asarray(fed) == 1
            ones = rows(cursors, 1, riding.astype(int))
            (alone,), stepped = window["pallas"](ones, before["pallas"])
            stepped = host(stepped)
            report[case] = {
                "fed": fed,
                "out_max_abs_err_vs_xla": float(err.max(initial=0)),
                "out_max_abs": float(np.abs(plain).max(axis=(1, 3))[real]
                                     .max(initial=0)),
                "out_finite": bool(np.isfinite(out).all()),
                "rings_equal_xla": all(
                    np.array_equal(a, b) for a, b in zip(new[:2], same[:2])),
                "summaries_max_abs_err_vs_xla": float(max(
                    np.abs(a - b).max() for a, b in zip(new[2:4], same[2:4]))),
                "cursors_equal_xla": bool(np.array_equal(new[4], same[4])),
                "cursors": new[4].ravel().tolist(),
                "dead_slots_pools_untouched": all(
                    np.array_equal(a[dead], b[dead])
                    for a, b in zip(old[:4], new[:4])),
                "riders_row_equals_s1_program": bool(np.array_equal(
                    out[riding, :, 0], host([alone])[0][riding, :, 0])),
                "riders_state_equals_s1_program": all(
                    np.array_equal(a[riding], b[riding])
                    for a, b in zip(new, stepped)),
            }
    # the window program alone, its pools donated, by what it is fed:
    # eight launches behind each other (a model's eight layers) a sample
    fn = opdef.variant_fn("pallas")
    timed = jax.jit(lambda ins, pools, cursor: fn(
        attrs, ins, pools + [cursor], False, None), donate_argnums=(1,))
    *pools, cursor = filled(window["pallas"])
    report["window_program_ms_p50"] = {}
    for case in _FED:
        ins = rows(cursors, S, _FED[case](S)[:B])
        ms = []
        for sample in range(3 + ns.times):
            t0 = time.perf_counter()
            for _ in range(8):
                _, (*pools, _moved) = timed(ins, pools, cursor)
            jax.block_until_ready(pools)
            if sample >= 3:
                ms.append((time.perf_counter() - t0) * 1e3 / 8)
        report["window_program_ms_p50"][case] = statistics.median(ms)
        if ns.trace:
            # the device's own line: every operation's ms a launch
            from chipbench import trace
            where = tempfile.mkdtemp(prefix="eva_ride_check_")
            with jax.profiler.trace(where):
                for _ in range(8):
                    _, (*pools, _moved) = timed(ins, pools, cursor)
                jax.block_until_ready(pools)
            events = trace.flatten(where)
            shutil.rmtree(where, ignore_errors=True)
            ops = {}
            for e in events:
                if e["line"] == trace.OP_LINE \
                        and e["plane"] == trace.device_planes(events)[0]:
                    name = trace._short(e["name"], 40)
                    ops[name] = ops.get(name, 0.0) + e["dur_ns"] / 8e6
            top = sorted(ops.items(), key=lambda kv: -kv[1])
            report.setdefault("device_ms_a_launch", {})[case] = {
                "sum": round(sum(ops.values()), 4),
                "eva_": round(sum(v for k, v in ops.items()
                                  if k.startswith("eva_")), 4),
                "ops": {k: round(v, 4) for k, v in top[:24]}}
    print(json.dumps(report))
    if not rides:
        return 0
    ok = all(r["rings_equal_xla"] and r["cursors_equal_xla"]
             and r["dead_slots_pools_untouched"] and r["out_finite"]
             and r["riders_row_equals_s1_program"]
             and r["riders_state_equals_s1_program"]
             and r["out_max_abs_err_vs_xla"] <= 0.02 * r["out_max_abs"] + 1e-5
             and r["summaries_max_abs_err_vs_xla"] <= 0.05
             for r in report.values() if isinstance(r, dict) and "fed" in r)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
