#!/usr/bin/env python3
"""By hand, on the chip: a window inside the row budget through the
PACKED window program, at a fed configuration's published widths.

``chipbench/serve_runner.py::check_reference`` feeds its sequences whole
windows (``step`` without ``fed``), so the comparison that decides
``correct`` reaches only the whole-window program; a serving window -
one slot prefilling, the others riding with a token each - runs the
packed form of the same graph (``models/transformer.py``'s
``packed_window``, ``ops/rows.py``), whose head runs over each slot's
last fed row and which returns that row alone, ``(slots, 1, V)``. Here
slot 0 prefills a sequence to
16,384 positions (or as many whole windows as leave one more inside
the configuration's capacity) and one window more while every other slot
of the top rung rides each window with one token of its own; once
through the
packed program (``fed`` sums to S + slots - 1 <= R) and once, from
cursor 0 again, through the whole-window program fed the same. Each
slot's last fed row - all that the packed program hands back - packed
against whole: slot 0's in every window of the schedule, the riders' in
the last; slot 0's row at the last position (and of the whole-window
program its last sixteen) against the architecture's plain float32
reference under its ``LOGIT_TOL``; whether the rows each path wrote to
the positional pools are equal (for a graph that carries state no
position indexes - ``granite-4.0-h-micro``'s and ``ling-3.0-flash``'s
convolution tails and recurrent states - slot 0's whole state after the
last window, every family); and the median window's time on the
host's clock in either form. ``--part N`` feeds slot 1 N rows of a sequence of its own a
window in place of one token, so that a whole chunk, a part of a chunk
and riders share one dispatch. Prints one JSON line.

    python3 tools/window_pack_check.py
        --config a.x-k1|glm-5.2|xing4.0-29b-a4b|cerebras-gpt-1.3b|olmoe-1b-7b
                 |granite-4.0-h-micro|ling-3.0-flash|granite-4.0-h-small
                 |trinity-mini|nemotron-3-nano-30b-a3b
        [--seed N] [--part N] [--context N] [--rehearse]

``--context N`` prefills N positions (whole windows) in place of all the
capacity allows: ``granite-4.0-h-small``'s reference attends all
positions at once in float32, 8.6 GB at its capacity of 8,192 beside
9.5 GB of parameters, so it is held to ``--context 3840``.

``--rehearse`` runs the configuration's tiny fixture on the CPU
(chipbench/tests/fixtures: a context of 64, windows of 16; for the
Cerebras configuration, which has no fixture there, ``_TINY_GPT2``
below); no number of it is a device number."""
import argparse
import functools
import gc
import inspect
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_TINY = {"a.x-k1": ("axk1", "tiny-axk1.json"),
         "glm-5.2": ("glm_dsa", "tiny-glm.json"),
         "xing4.0-29b-a4b": ("xing4", "tiny-xing4.json"),
         "olmoe-1b-7b": ("olmoe", "tiny-olmoe.json"),
         "granite-4.0-h-micro": ("granite_hybrid", "tiny-granite.json"),
         "ling-3.0-flash": ("ling_hybrid", "tiny-ling.json"),
         "granite-4.0-h-small": ("granite_moe_hybrid",
                                 "tiny-granite-small.json"),
         "trinity-mini": ("afmoe", "tiny-afmoe.json"),
         "nemotron-3-nano-30b-a3b": ("nemotron_h", "tiny-nemotron.json"),
         "cerebras-gpt-1.3b": None}

#: the Cerebras configuration cut to a rehearsal's size (learned
#: positions, a tied head; 4 slots of 8 rows pack to 16)
_TINY_GPT2 = {"name": "tiny-gpt2", "arch": "gpt2", "n_embd": 64,
              "n_layer": 2, "n_head": 4, "n_inner": 256, "vocab_size": 128,
              "n_positions": 128, "position_embedding": "learned",
              "compute_dtype": "bfloat16", "capacity": 128,
              "prefill_chunk": 8, "ladder": [1, 2, 4]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=sorted(_TINY), required=True)
    ap.add_argument("--seed", type=int, default=2147480243)
    ap.add_argument("--part", type=int, default=0)
    ap.add_argument("--context", type=int, default=16384)
    ap.add_argument("--rehearse", action="store_true")
    ns = ap.parse_args(argv)
    from chipbench import common, manifest
    common.set_caches()
    if ns.rehearse and _TINY[ns.config] is None:
        cfg = dict(_TINY_GPT2)
    else:
        path = os.path.join(ROOT, "chipbench", "tests", "fixtures",
                            _TINY[ns.config][0], "configs",
                            _TINY[ns.config][1]) if ns.rehearse else \
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

    S = cfg["prefill_chunk"]
    n_cmp = min(16, S)
    ctx = min(ns.context, cfg["capacity"] - S) if not ns.rehearse else 3 * S
    windows = ctx // S + 1
    assert ctx % S == 0 and windows * S <= cfg["capacity"]
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
    budget = drv.window_budget(S)
    assert budget is not None and S + top - 1 <= budget
    setup_s = time.perf_counter() - t0

    rng = np.random.default_rng([ns.seed % (1 << 32), 43])
    seq = rng.integers(0, cfg["vocab_size"], (1, windows * S)) \
        .astype(np.int32)
    riders = rng.integers(0, cfg["vocab_size"], (top, windows)) \
        .astype(np.int32)
    fed = np.asarray([S] + [1] * (top - 1), np.int32)
    part = None
    if ns.part:
        assert top > 1 and 1 < ns.part <= S \
            and S + ns.part + top - 2 <= budget, (ns.part, budget)
        fed[1] = ns.part
        part = rng.integers(0, cfg["vocab_size"], windows * ns.part) \
            .astype(np.int32)

    # each slot's last fed row: all that a packed window hands back
    last = np.maximum(fed - 1, 0)

    def run(whole):
        """Every window of the schedule through one form of the window
        program: ``(slot 0's last fed row of every window, every slot's
        of the last window, the last rows of slot 0's last window that
        this form returns - sixteen whole, one packed -, the rows
        written, the windows' seconds, the rows the program and its
        head ran over)``."""
        drv.active[:] = False
        drv.rewind_many(list(range(top)), [0] * top)
        for slot in range(top):
            drv.join(slot)
        # the driver takes the packed program whenever ``fed`` fits its
        # budget: hide it for the whole-window pass (nothing public
        # chooses, by design)
        hidden = drv._packed.pop(S) if whole else None
        seconds, firsts, out = [], [], None
        try:
            for w in range(windows):
                out = None      # the window before's logits go first
                tokens = np.zeros((top, S), np.int32)
                tokens[0] = seq[0, w * S:(w + 1) * S]
                tokens[1:, 0] = riders[1:, w]
                if part is not None:
                    tokens[1, :ns.part] = part[w * ns.part:(w + 1) * ns.part]
                t = time.perf_counter()
                out = drv.step(tokens, fed=fed)
                drv.release_outputs()   # 2 GB of logits at a whole
                out.asjax().block_until_ready()     # vocabulary
                seconds.append(time.perf_counter() - t)
                ran = drv.last_program_rows, drv.last_head_rows
                firsts.append(np.asarray(
                    out.asjax()[0, S - 1 if whole else 0], np.float32))
        finally:
            if hidden is not None:
                drv._packed[S] = hidden
        assert out.shape[:2] == ((top, S) if whole else (top, 1)), out.shape
        logits = out.asnumpy().astype(np.float32)
        if drv.positional:
            written = [np.asarray(a, np.float32)[..., ctx:, :]
                       for a in drv.capture_rows(0, windows * S).values()]
        else:       # state no position indexes: slot 0's, every family
            written = [np.asarray(cell.asjax()[0], np.float32)
                       for family in sorted(drv.state_bytes)
                       if family != "cursor"
                       for _nm, cell in drv._cells(family)]
        return (np.stack(firsts),
                logits[np.arange(top), last] if whole else logits[:, 0],
                logits[0, S - n_cmp:] if whole else logits[0],
                written, seconds, ran)

    first_p, ends_p, tail_p, rows_p, s_p, ran_p = run(whole=False)
    first_w, ends_w, tail_w, rows_w, s_w, ran_w = run(whole=True)
    assert (ran_p, ran_w) == ((budget, top), (top * S, top * S)), \
        (ran_p, ran_w)

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

    try:
        forward = arch._reference.forward
        if "tail" in inspect.signature(forward).parameters:
            fwd = functools.partial(forward, config=rcfg, tail=n_cmp)
        else:       # a reference of every position: its last rows
            fwd = lambda p, t: forward(               # noqa: E731
                p, t, config=rcfg)[:, -n_cmp:]
        want = np.asarray(jax.jit(fwd)(params, seq))[0]
        report = {"packed_vs_reference": against(want[-1:], tail_p),
                  "whole_vs_reference": against(want, tail_w),
                  "max_abs_logit": float(np.abs(want).max())}
        ok_ref = report["packed_vs_reference"]["max_err_over_bound"] <= 1.0
    except jax.errors.JaxRuntimeError as e:     # the device's memory
        report = {"reference": f"failed: {type(e).__name__}: "
                               f"{str(e)[:300]}"}
        ok_ref = False
    device = jax.devices()[0]
    # the first window of either pass may compile (the whole-window
    # program is not warmed where there is a packed one)
    print(json.dumps({
        "window_pack_check": cfg["name"], "seed": ns.seed, "context": ctx,
        "rows_vs_whole": windows + top - 1, "rows_vs_reference": 1,
        "window": S, "slots": top, "budget_rows": budget,
        "head_rows": ran_p[1], "fed": fed.tolist(),
        "packed_vs_whole_max_abs_diff": float(
            np.abs(first_p - first_w).max()),
        "packed_vs_whole_in_tol_units": float(
            (np.abs(first_p - first_w)
             / (tol + tol * np.abs(first_w))).max()),
        "packed_vs_whole_argmax_equal": int(
            (first_p.argmax(1) == first_w.argmax(1)).sum()),
        "riders_packed_vs_whole_max_abs_diff": float(
            np.abs(ends_p[1:] - ends_w[1:]).max()),
        "riders_packed_vs_whole_in_tol_units": float(
            (np.abs(ends_p[1:] - ends_w[1:])
             / (tol + tol * np.abs(ends_w[1:]))).max()),
        "riders_argmax_equal": int(
            (ends_p[1:].argmax(1) == ends_w[1:].argmax(1)).sum()),
        "rows_written_equal": bool(all(
            np.array_equal(a, b) for a, b in zip(rows_p, rows_w))),
        "rows_written_max_abs_diff": float(max(
            np.abs(a - b).max() for a, b in zip(rows_p, rows_w))),
        "packed_window_ms_p50": 1e3 * statistics.median(s_p[1:]),
        "whole_window_ms_p50": 1e3 * statistics.median(s_w[1:]),
        "packed_window_ms_last": 1e3 * s_p[-1],
        "whole_window_ms_last": 1e3 * s_w[-1],
        "tolerance": tol, **report, "setup_s": setup_s, "ok": bool(ok_ref),
        "platform": device.platform, "device_kind": device.device_kind,
        "rehearsal": ns.rehearse}), flush=True)
    return 0 if ok_ref else 1


if __name__ == "__main__":
    sys.exit(main())
