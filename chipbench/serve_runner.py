"""Runner for configurations of kind "serve": a decoder behind
``mxnet_tpu.serve.serve_decoder`` under a traffic mix.

What is served - the symbol, its parameters, its plain reference with
its tolerance, its costs - is the configuration's architecture
(``archs/<arch>.py``; README.md, "The architecture interface"). Here
are the entry point and its window.

Set-up (all of it counted in ``setup_s``): weights made on the device
from the seed in one jitted call, ``serve_decoder`` binds, autotunes
(first run of a checkout only) and warms every rung's S=1 and window
program, the cursor pokes are warmed for every row count, the heap is
frozen, the clients start, and the lead-in lets whole blocks complete.
Then the window opens. ``correct`` is decided after it.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import math
import threading
import time

import numpy as np

from . import common, critical_path, manifest, stats, traffic as traffic_mod


class _Record:
    """One request as its client saw it (time.perf_counter)."""

    __slots__ = ("req", "t_submit", "stamps", "t_done", "error", "n_tokens")

    def __init__(self, req):
        self.req = req
        self.t_submit = None
        self.stamps = []            # one per output token, at emission
        self.t_done = None
        self.error = None
        self.n_tokens = None

    def on_token(self, _handle, _token, _index):
        self.stamps.append(time.perf_counter())


class _Load:
    """The clients. Closed loop: ``clients`` callers, each sends the
    next request of the common sequence the moment its last one
    completes - from the handle's done callback, which the scheduler
    runs before its next iteration, so no thread's wake-up decides which
    iteration admits the request and a run's schedule of iterations
    follows from the mix alone. Open loop: one thread submits on the
    schedule."""

    def __init__(self, sched, mix, vocab, seed, horizon_s):
        self.sched = sched
        self.mix = mix
        self.records = []
        self._gen = traffic_mod.requests(mix, vocab, seed)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.completed = 0
        self.done_cond = threading.Condition()
        self.threads = []
        if mix["kind"] == "open_loop":
            self._due = traffic_mod.arrivals(mix, seed, horizon_s)
            self.threads.append(threading.Thread(
                target=self._open, name="chipbench-arrivals", daemon=True))
        elif mix["kind"] != "closed_loop":
            raise SystemExit(f"chipbench: traffic kind {mix['kind']!r} "
                             "is not a serving mix")

    def start(self):
        self.t0 = time.perf_counter()
        if self.mix["kind"] == "closed_loop":
            for _ in range(int(self.mix["clients"])):
                self._send_next()
        for t in self.threads:
            t.start()

    def stop(self):
        self._stop.set()

    def join(self):
        for t in self.threads:
            t.join(timeout=120)

    def _next(self):
        with self._lock:
            rec = _Record(next(self._gen))
            self.records.append(rec)
        return rec

    def _send_next(self, due=None):
        """Submit the next request; its done callback records the
        outcome and, in a closed loop, sends the caller's next one."""
        rec = self._next()
        rec.t_submit = time.perf_counter()
        if due is not None:     # open loop: time it from when it was DUE
            rec.t_submit = min(rec.t_submit, due)
        try:
            handle = self.sched.submit(
                rec.req.prompt, max_new_tokens=rec.req.max_new, eos_id=None,
                prefix_id=rec.req.prefix_id)
        except Exception as e:                # e.g. QueueFullError
            self._done(rec, error=e)
            return
        handle.add_token_callback(rec.on_token)
        handle.add_done_callback(lambda h, rec=rec: self._on_done(rec, h))

    def _on_done(self, rec, handle):
        err = handle.exception()
        self._done(rec, error=err, n_tokens=None if err is not None
                   else len(handle.tokens))
        if self.mix["kind"] == "closed_loop" and not self._stop.is_set():
            self._send_next()

    def _done(self, rec, error=None, n_tokens=None):
        if error is not None:                 # a failed request is data
            rec.error = f"{type(error).__name__}: {error}"
        rec.n_tokens = n_tokens
        rec.t_done = time.perf_counter()
        with self.done_cond:
            self.completed += 1
            self.done_cond.notify_all()

    def _open(self):
        for due in self._due:
            delay = self.t0 + due - time.perf_counter()
            if (delay > 0 and self._stop.wait(delay)) or self._stop.is_set():
                break
            self._send_next(due=self.t0 + due)

    def wait_completed(self, n, timeout):
        with self.done_cond:
            return self.done_cond.wait_for(lambda: self.completed >= n,
                                           timeout=timeout)


def served_params(engine):
    """The parameter values the engine serves, by name (device arrays;
    no copy)."""
    exe = engine._bm._leader._exec_group.executor
    return {n: c.asjax() for n, c in exe.arg_dict.items()
            if n not in engine.data_names}


def decode_step(arch, config, drv, S, n_cmp):
    """``(L, mask)``: the tokens a slot is fed in one decode dispatch
    and the id that stands for a token not yet chosen, as the
    architecture states them (``manifest.ARCH_OPTIONAL``; 1 and None
    where it states neither). An engine that cannot be held to them is
    refused by name."""
    stated = {n: getattr(arch, n)(config)
              for n in manifest.ARCH_OPTIONAL["serve"] if hasattr(arch, n)}
    L = int(stated.get("decode_step_len", 1))
    mask = stated.get("mask_token")
    lens = sorted({1, *drv.window_lens})
    if L not in lens:
        raise SystemExit(
            f"chipbench: decode_step_len {L}: the engine has programs of "
            f"{lens} tokens a slot (drv.window_lens) and none of {L}")
    if n_cmp % L or S % L:
        # blocks are counted from position 0: every feed of the check
        # begins on a block's edge only if L divides both
        raise SystemExit(
            f"chipbench: decode_step_len {L} does not divide the "
            f"{n_cmp} positions compared and the window of {S}")
    if mask is not None and not drv.positional:
        raise SystemExit(
            f"chipbench: mask_token {mask}: a masked feed is taken back "
            "by rewind_many, and this engine is not positional (a "
            "carried state rewinds to 0 alone)")
    return L, None if mask is None else int(mask)


def _feed(rung, width, rows):
    """``(rung, width)`` ids: ``rows[i]`` at the head of slot ``i``'s
    row, 0 elsewhere."""
    tokens = np.zeros((rung, width), np.int32)
    for slot, ids in enumerate(rows):
        tokens[slot, :len(ids)] = ids
    return tokens


def _over(got, want, tol):
    """``(max |got - want|, max of it over its bound)``; a NaN stays
    one, and is under no limit."""
    err = np.abs(got - want)
    return float(np.max(err)), float(np.max(err / (tol + tol * np.abs(want))))


@contextlib.contextmanager
def _no_detail():
    """A reference call traced under it reports nothing: the
    architectures hand their emulation and controls over the call's
    tail to ``jax.experimental.io_callback`` (the ``reference_detail``
    line) and add its zero to the logits, which do not depend on them.
    With the callback a zero of its own they are dead code, and a call
    whose tail is other positions than the line's costs one forward and
    not five to eight."""
    import jax
    import jax.numpy as jnp

    def silent(_callback, result, *_args, **_kw):
        return jax.tree.map(lambda r: jnp.zeros(r.shape, r.dtype), result)

    real = jax.experimental.io_callback
    jax.experimental.io_callback = silent
    try:
        yield
    finally:
        jax.experimental.io_callback = real


def check_reference(engine, arrays, config, seed, arch):
    """The programs the window timed against the architecture's plain
    float32 reference's full forward, on two seeded sequences, within
    its ``LOGIT_TOL``: |served - reference| <= TOL + TOL * |reference|
    on every compared logit. Three parts, in this order (nothing is
    rewound between them: a carried state rewinds to 0 alone):

    1. prefill through the top rung's whole-window program (``step``
       without ``fed``), the last 16 rows compared;
    2. decode 16 positions at the step length the architecture states
       (``decode_step``; S=1 steps where it states none), every row
       compared - where it states a mask token each step is fed twice:
       a seeded subset of its columns masked, those rows compared with
       the reference's forward over the same ids, the cursors put back,
       then the clean ids;
    3. two windows inside the budget with ``fed`` given, as a serving
       window is launched (the packed program where the rung has one):
       one sequence its next S tokens and the other its next L, then
       the roles swapped; the first sequence's two rows compared - a
       chunk's last row and a rider's row.

    A reference row is read only from the last 32 positions of the call
    that produced it (several architectures return no others); parts 1
    and 2 are one call on the parent's own tokens, part 3 one more at a
    second length over the first sequence alone (fewer tokens than the
    first call's, so that it fits beside a live engine wherever that
    one fits), traced under ``_no_detail``. Returns ``(ok, report)``;
    the report's older keys are over parts 1 and 2 alone."""
    import jax
    t0 = time.perf_counter()
    rung = engine.ladder.max
    drv = engine.driver(rung)
    S = max(drv.window_lens) if drv.window_lens else 1
    n_cmp = min(16, S)
    L, mask = decode_step(arch, config, drv, S, n_cmp)
    V = config["vocab_size"]

    def whole_windows(room):
        return max(1, min(4, room // S))

    windows = whole_windows(engine.capacity - n_cmp - S - L)
    t_pre = S * windows
    P = t_pre + n_cmp
    if P + S + L > engine.capacity:
        raise SystemExit(
            f"chipbench: a capacity of {engine.capacity} has no room for "
            f"the check's {P} + {S} + {L} positions")
    rng = np.random.default_rng([int(seed) % (1 << 32), 11])
    n_seq = min(2, rung)
    slots = list(range(n_seq))
    # the parent's own draw first, so that parts 1 and 2 are its tokens
    seqs = np.concatenate(
        [rng.integers(0, V, (n_seq, P)), rng.integers(0, V, (n_seq, S + L))],
        axis=1).astype(np.int32)
    steps = n_cmp // L
    if mask is not None:        # the columns each masked feed hides
        hidden = rng.random((steps, L)) < 0.5
        hidden[~hidden.any(axis=1), rng.integers(0, L)] = True
    drv.active[:] = False
    drv.rewind_many(list(range(rung)), [0] * rung)
    for slot in slots:
        drv.join(slot)

    def step(tokens, read=True, **fed):
        """One dispatch; the sequences' rows on the host. The program
        lets go of what it made at once (a whole window's logits are
        gigabytes, and the reference needs the room)."""
        out = drv.step(tokens, **fed)
        rows = out.asnumpy()[:n_seq].astype(np.float32) if read else None
        del out
        drv.release_outputs()
        return rows

    got = np.zeros((n_seq, 2 * n_cmp, V), np.float32)
    for w in range(windows):
        rows = step(_feed(rung, S, seqs[:, w * S:(w + 1) * S]),
                    read=w == windows - 1)
    got[:, :n_cmp] = rows[:, S - n_cmp:]
    got_masked = []
    for j in range(steps):
        at = t_pre + j * L
        clean = seqs[:, at:at + L]
        if mask is not None:    # a feed that must leave nothing behind
            got_masked.append(step(
                _feed(rung, L, np.where(hidden[j], mask, clean))))
            drv.rewind_many(slots, [at] * n_seq)
        got[:, n_cmp + j * L:n_cmp + (j + 1) * L] = \
            step(_feed(rung, L, clean))
    # a chunk and a rider in one dispatch, then the roles swapped
    at = [P] * n_seq
    got_fed, program_rows = [], []
    for widths in ((S, L), (L, S)):
        widths = widths[:n_seq]
        fed = np.zeros(rung, np.int64)
        fed[:n_seq] = widths
        rows = step(_feed(rung, S, [seqs[s, at[s]:at[s] + n]
                                    for s, n in enumerate(widths)]),
                    fed=fed)        # (n_seq, 1, V) packed, else (n_seq, S, V)
        program_rows.append(int(drv.last_program_rows))
        for s, n in enumerate(widths):
            at[s] += n
        # the first sequence's last fed row: window 1's chunk, window
        # 2's rider (the second sequence is the other half of each)
        got_fed.append((at[0] - 1,
                        rows[0, 0 if rows.shape[1] == 1 else widths[0] - 1]))
    for slot in slots:
        drv.leave(slot)
    drv.rewind_many(list(range(rung)), [0] * rung)
    t1 = time.perf_counter()

    fwd = jax.jit(functools.partial(arch.reference_logits, cfg=config))
    tol = arch.LOGIT_TOL
    want = np.asarray(fwd(arrays, seqs[:, :P]))[:, t_pre - n_cmp:P]
    err = np.abs(got - want)
    bound = tol + tol * np.abs(want)
    ok = bool(np.all(err <= bound))       # a NaN fails
    report = {"sequences": n_seq, "tokens": int(P),
              "positions_compared": 2 * n_cmp,
              "max_abs_err": float(np.max(err)),
              "max_err_over_bound": float(np.max(err / bound)),
              "max_abs_logit": float(np.max(np.abs(want))),
              "tolerance": tol, "decode_step_len": L,
              "masked_feeds": len(got_masked)}
    parent = whole_windows(engine.capacity - n_cmp)
    if windows != parent:   # room made for part 3: other tokens than before
        report["tokens_before_fed_windows"] = S * parent + n_cmp
    overs = []
    for j, rows_j in enumerate(got_masked):   # the first call's shape
        ids = seqs[:, :P].copy()
        at = t_pre + j * L
        ids[:, at:at + L] = np.where(hidden[j], mask, ids[:, at:at + L])
        overs.append(_over(
            rows_j, np.asarray(fwd(arrays, ids))[:, at:at + L], tol)[1])
    if overs:
        report["masked_max_err_over_bound"] = max(overs)
        ok = ok and all(over <= 1.0 for over in overs)
    t2 = time.perf_counter()
    # the first length's program leaves the chip before the second's is
    # loaded: beside a live engine there is room for one
    fwd.clear_cache()
    with _no_detail():
        want = np.asarray(fwd(arrays, seqs[:1, :P + S + L]))[0]
    fed_err, fed_over = _over(np.stack([r for _p, r in got_fed]),
                              np.stack([want[p] for p, _r in got_fed]), tol)
    report["fed_windows"] = {
        "program_rows": program_rows,
        "packed": [r < rung * S for r in program_rows],
        "rows_compared": len(got_fed), "max_abs_err": fed_err,
        "max_err_over_bound": fed_over}
    report["seconds"] = {"steps": t1 - t0, "reference": t2 - t1,
                         "fed_windows_reference": time.perf_counter() - t2}
    return ok and fed_over <= 1.0, report


def _counters(model):
    """``{name: value}`` of every counter the program has registered
    under the served model's label."""
    from mxnet_tpu import telemetry
    out = {}
    for m in telemetry.metrics.all_metrics():
        if isinstance(m, telemetry.Counter) and ("model", model) in m.labels:
            out[m.name] = out.get(m.name, 0) + m.value
    return out


#: the ``window`` line's counters; ``obs["counters"]`` holds all of them
_COUNTERS = ("serve.decode.tokens", "serve.decode.iterations",
             "serve.decode.prefill.chunks", "serve.decode.requests",
             "serve.decode.responses", "serve.decode.errors",
             "serve.decode.migrations")


def run(cell, seed, seconds, trace, device, t_start, rehearse=False):
    import jax
    watch = common.CompileWatch()
    import mxnet_tpu as mx
    from mxnet_tpu.telemetry import flightrec
    arch = manifest.load_arch(cell)

    cfg, mix = cell.config, cell.traffic
    phases = {"import_s": time.perf_counter() - t_start}
    capacity = cfg["capacity"]
    context = mx.cpu(0) if rehearse else mx.tpu(0)
    gen = functools.partial(arch.decode_symbol, cfg)    # step_len -> Symbol

    t = time.perf_counter()
    top = max(cfg["ladder"])
    args = arch.make_params(gen(1), arch.data_shapes(cfg, top, 1), seed, cfg)
    phases["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    if trace:
        flightrec.configure(capacity=400000)
    sched = mx.serve.serve_decoder(
        gen(1), args, name=cfg["name"], capacity=capacity,
        ladder=cfg["ladder"], context=context,
        compute_dtype=cfg["compute_dtype"], symbol_gen=gen,
        prefill_chunk=cfg["prefill_chunk"], start=False)
    engine = sched.engine
    del args
    # the scheduler's cursor pokes take 1..rung rows: one tiny program
    # for each count, warmed here so that none compiles in the window
    for rung in engine.ladder.sizes:
        drv = engine.driver(rung)
        for k in range(1, rung + 1):
            drv.rewind_many(list(range(k)), [0] * k)
    phases["bind_warm_s"] = time.perf_counter() - t
    tier = common.kernel_tier_table()
    common.say("kernel_tier", decisions=tier, compile_cache=dict(watch.cache),
               warmup_compiles=engine.warmup_compiles)

    gc.collect()
    gc.freeze()
    t = time.perf_counter()
    load = _Load(sched, mix, cfg["vocab_size"], seed,
                 horizon_s=seconds + 600)
    load.start()        # a closed loop's first requests queue up, and
    sched.start()       # the first iteration admits them together
    n_block = len(mix["block"])
    if mix["kind"] == "closed_loop":
        if not load.wait_completed(mix.get("lead_in_blocks", 1) * n_block,
                                   timeout=600):
            raise SystemExit("chipbench: the lead-in did not complete")
    else:
        time.sleep(float(mix.get("lead_in_s", 5.0)))
    phases["lead_in_s"] = time.perf_counter() - t

    # ------------------------------------------------------------ window
    name = cfg["name"]
    before = _counters(name)
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    tracer = common.Tracer(cell.name) if trace else None
    pos_samples = []
    if tracer is not None:
        time.sleep(min(1.0, seconds / 4))
        tracer.start()
        t_end = time.perf_counter() + min(float(mix.get("trace_seconds", 4)),
                                          seconds / 2)
        drv = engine.driver(engine.ladder.max)
        while time.perf_counter() < t_end:
            live = drv.pos[drv.active]
            if live.size:
                pos_samples.append(float(live.mean()))
            time.sleep(0.05)
        tracer.stop()
    remaining = t_open + seconds - time.perf_counter()
    if remaining > 0:
        time.sleep(remaining)
    t_close = time.perf_counter()
    counters = {c: v - before.get(c, 0) for c, v in _counters(name).items()}
    ring = [r for r in flightrec.get_records()
            if t_open * 1e6 <= r.get("ts_us", 0) < t_close * 1e6]
    compiles_in_window = watch.backend_compiles(t_open, t_close)

    # lead-out: the load stays on until every request submitted inside
    # the window has its first token, so time to first token is taken
    # under the same load for all of them
    def pending():
        return [r for r in list(load.records)
                if r.t_submit is not None and t_open <= r.t_submit < t_close
                and not r.stamps and r.t_done is None]
    t_limit = time.perf_counter() + 120
    while pending() and time.perf_counter() < t_limit:
        time.sleep(0.05)
    load.stop()
    records = list(load.records)
    finished = [r for r in records if r.t_done is not None]
    stats_now = sched.stats()
    sched.stop(drain=False)
    load.join()

    # ----------------------------------------------------------- metrics
    stamps = [r.stamps for r in records]
    in_window = [r for r in records if r.t_submit is not None
                 and t_open <= r.t_submit < t_close]
    wrong_len = [r for r in finished if r.error is None
                 and r.n_tokens != r.req.max_new]
    failed = [r for r in in_window
              if (r.error is not None and r.t_done is not None
                  and r.t_done < t_close) or r in wrong_len]
    ttft = stats.ttfts([(r.t_submit, r.stamps[0] if r.stamps else None,
                         r in failed) for r in records], t_open, t_close)
    gaps = stats.token_gaps(stamps, t_open, t_close)
    values = {
        "serve_tokens_per_s": stats.tokens_per_s(stamps, t_open, t_close),
        "serve_ttft_p90_ms": 1e3 * stats.percentile(ttft, 90)
        if ttft else None,
        "serve_tpot_p95_ms": 1e3 * stats.percentile(gaps, 95)
        if gaps else None,
        "setup_s": setup_s,
    }
    n_tokens = sum(len(stats.in_window(s, t_open, t_close)) for s in stamps)
    common.say("window", seconds=t_close - t_open, requests=len(in_window),
               ttft_samples=len(ttft), gap_samples=len(gaps),
               tokens=n_tokens, blocks=len(in_window) / n_block,
               finished=len(finished), failed=len(failed),
               wrong_length=len(wrong_len),
               compiles_in_window=compiles_in_window[:8],
               ttft_ms={q: 1e3 * stats.percentile(ttft, q)
                        for q in (50, 75, 90, 95)} if ttft else None,
               tpot_ms={q: 1e3 * stats.percentile(gaps, q)
                        for q in (50, 75, 90, 95, 97, 99)} if gaps else None,
               counters={c: counters.get(c, 0) for c in _COUNTERS},
               rung=stats_now["rung"], end_to_end=values, **device,
               rehearsal=rehearse)
    common.say("setup", setup_s=setup_s, **phases,
               compile_events_s=watch.seconds(),
               memory_stats=common.memory_stats(),
               slow_compiles=watch.slowest(),
               compile_cache=dict(watch.cache),
               autotuned=common.autotuned_sites(tier))

    peak = common.memory_peak_bytes(cell.chips)   # before the reference's
    ok_ref, report = check_reference(engine, served_params(engine), cfg,
                                     seed, arch)
    common.say("reference", ok=ok_ref, **report)
    correct = (ok_ref and not failed and not wrong_len
               and not compiles_in_window and n_tokens > 0)
    compared = {
        "reference_err_over_bound": (report["max_err_over_bound"], 1.0),
        "fed_windows_err_over_bound":
            (report["fed_windows"]["max_err_over_bound"], 1.0),
        "requests_failed": (len(failed), 0),
        "wrong_length": (len(wrong_len), 0),
        "compiles_in_window": (len(compiles_in_window), 0),
        "tokens_in_window_at_least": (n_tokens, 1)}
    if "masked_max_err_over_bound" in report:
        compared["masked_err_over_bound"] = \
            (report["masked_max_err_over_bound"], 1.0)

    if trace:
        live_rows = float(np.mean(pos_samples)) if pos_samples else 0.0
        top = engine.ladder.max
        obs = {
            "counters": counters, "ring": ring,
            "series": {"gap_s": gaps,
                       "ttft_s": [v for v in ttft if math.isfinite(v)]},
            "events": tracer.events, "device_kind": device["kind"],
            "chips": cell.chips,
            "cost": arch.costs(cfg, top, cfg["prefill_chunk"], live_rows),
        }
        common.say("traced", live_rows=live_rows, counters=counters,
                   ring_records=len(ring),
                   events=len(tracer.events or []))
        critical_path.account(obs)      # its line; it decides nothing
        metrics = common.per_layer_metrics(cell, obs)
    else:
        metrics = common.end_to_end_metrics(cell, values)
    common.result_line(correct, len(in_window), len(failed), metrics,
                       device, peak, tracer=tracer, compared=compared)
