"""One span API onto the profiler's clock (ISSUE 25).

``telemetry.span`` is a ``jax.profiler.TraceAnnotation`` whenever jax is
in the process, so the host phases of ``DecodeScheduler._iterate``,
``Module.fit`` and the ``PrefetchingIter`` producer land in the device
trace under fixed names - the contract the benchmark's readers
(chipbench/spans.py) match letter for letter. Pinned here on the CPU:
the names, their nesting and their counts with the span buffer
DISABLED, the phase fields of the ``serve.decode.step`` ring record,
the jax-free fallback, the backend-compile counter (ROADMAP D11) and
the program names.
"""
import glob
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tm
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.serve import FakeClock
from mxnet_tpu.serve.clock import MonotonicClock
from mxnet_tpu.telemetry import flightrec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, D, L, H, T, S = 64, 32, 2, 4, 32, 4     # tiny LM, window S
ITERS = STEPS = 3


@pytest.fixture(autouse=True)
def _tracer_off():
    tm.disable()
    yield
    tm.disable()


def _profiled(tmp, body):
    """Host events ``(thread, name, start_ns, end_ns, stats)`` of the
    JAX profiler's trace around ``body()`` (Python tracer off)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        # a thread is a line; two threads may share a line NAME
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                out.append((thread, ev.name.split("#")[0], ev.start_ns,
                            ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def _decode_symbol(step_len):
    return tfm.get_decode_symbol(
        vocab_size=V, d_model=D, n_layer=L, n_head=H, capacity=T,
        per_slot=True, step_len=step_len, max_seq_len=T)


def _scheduler(name, clock, ahead=False):
    """A tiny scheduler of one rung. Every dispatch is planned after
    its predecessor's commit (the synchronous order, which these tests
    pinned before ISSUE 46 and which stays the order of every dispatch
    that cannot run ahead) unless ``ahead``."""
    sym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L, n_head=H,
                         seq_len=8, include_loss=False, max_seq_len=T)
    mod = mx.mod.Module(sym, label_names=[])
    mod.bind([("data", (1, 8))], None, for_training=False)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          magnitude=2))
    args, _ = mod.get_params()
    sched = mx.serve.serve_decoder(
        _decode_symbol(1), args, name=name, capacity=T, ladder=[2],
        symbol_gen=_decode_symbol, prefill_chunk=S, start=False,
        clock=clock)
    if not ahead:
        sched._plan_ahead = lambda d, now: None
    return sched


@pytest.fixture(scope="module")
def decode_trace(tmp_path_factory):
    """Three ``pump()`` iterations of a tiny scheduler (two window
    iterations that prefill 9 tokens, then an S=1 one), profiled."""
    tm.disable()
    sched = _scheduler("spans-trace", MonotonicClock())
    sched.submit(np.arange(1, 10), max_new_tokens=4)
    events = _profiled(tmp_path_factory.mktemp("decode"),
                       lambda: sched.pump(max_iterations=ITERS))
    sched.pump()
    return events


@pytest.fixture(scope="module")
def fit_trace(tmp_path_factory):
    """Three steps of a tiny ``Module.fit`` over a ``PrefetchingIter``
    that stages onto the device, profiled from the iterator's start."""
    tm.disable()
    rng = np.random.RandomState(0)
    X = rng.rand(4 * STEPS, 10).astype("f")
    Y = (rng.rand(4 * STEPS) * 3).astype("f")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=3),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu(),
                        logger=logging.getLogger("spans_fit"))
    seen = []

    def body():
        it = mx.io.PrefetchingIter(mx.io.NDArrayIter(X, Y, batch_size=4),
                                   device=mx.cpu())
        mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1},
                batch_end_callback=lambda p: seen.append(p.nbatch))

    events = _profiled(tmp_path_factory.mktemp("fit"), body)
    assert seen == list(range(STEPS))
    # the epoch's end resets the iterator, whose new producer prefetches
    # again until the trace stops: keep the epoch itself
    epoch_end = max(e[3] for e in _named(events, "module.fit.data_wait"))
    return [e for e in events if e[2] < epoch_end]


def _named(events, name):
    return [e for e in events if e[1] == name]


def _assert_inside(events, child, parent):
    """Every ``child`` event lies inside a ``parent`` event of its own
    thread."""
    parents = _named(events, parent)
    for line, _n, start, end, _s in _named(events, child):
        assert any(p[0] == line and p[2] <= start and end <= p[3]
                   for p in parents), (child, "outside every", parent)


DECODE_PHASES = ("plan", "dispatch", "fetch", "commit", "rewind", "account")


@pytest.mark.parametrize("phase", DECODE_PHASES)
def test_decode_phase_once_per_iteration_inside_iter(decode_trace, phase):
    name = "serve.decode.iter." + phase
    assert len(_named(decode_trace, "serve.decode.iter")) == ITERS
    assert len(_named(decode_trace, name)) == ITERS
    _assert_inside(decode_trace, name, "serve.decode.iter")


def test_decode_iter_annotation_carries_the_plan(decode_trace):
    """``iter`` joins an annotation to its ring record; mode, window,
    rung and active are known once the plan phase has run. The
    dispatch phase encloses the executor's own span."""
    stats = [e[4] for e in sorted(_named(decode_trace, "serve.decode.iter"),
                                  key=lambda e: e[2])]
    assert [int(s["iter"]) for s in stats] == list(range(ITERS))
    assert [int(s["window"]) for s in stats] == [S, S, 1]
    assert {s["mode"] for s in stats} == {"window"}
    assert {int(s["rung"]) for s in stats} == {2}
    assert {int(s["active"]) for s in stats} == {1}
    runs = _named(decode_trace, "executor.run")
    assert len(runs) == ITERS and {e[4]["kind"] for e in runs} == \
        {"fwd_infer"}
    _assert_inside(decode_trace, "executor.run",
                   "serve.decode.iter.dispatch")


# ISSUE 36: the parts of dispatch and fetch, where the work happens
DRIVER_SPANS = ("decode.step.stage", "decode.step.launch",
                "decode.select_rows")


@pytest.mark.parametrize("name, parent", [
    (n, "serve.decode.iter.dispatch") for n in DRIVER_SPANS] + [
    ("serve.decode.iter.fetch.ids", "serve.decode.iter.fetch")])
def test_decode_part_once_per_iteration_inside_its_phase(decode_trace, name,
                                                         parent):
    assert len(_named(decode_trace, name)) == ITERS
    _assert_inside(decode_trace, name, parent)


def test_decode_parts_follow_each_other(decode_trace):
    """stage, launch (around the executor's own span) and select_rows in
    that order inside dispatch; ``account`` after ``rewind``, the last
    thing of the iteration."""
    def starts(name):
        return sorted((e[2], e[3]) for e in _named(decode_trace, name))
    order = DRIVER_SPANS + ("serve.decode.iter.fetch.ids",
                            "serve.decode.iter.commit",
                            "serve.decode.iter.rewind",
                            "serve.decode.iter.account")
    for k in range(ITERS):
        spans = [starts(n)[k] for n in order]
        for (_a, end), (start, _b) in zip(spans, spans[1:]):
            assert end <= start, (k, order)
    _assert_inside(decode_trace, "executor.run", "decode.step.launch")
    _assert_inside(decode_trace, "io.load_batch", "decode.step.launch")


def test_driver_alone_writes_its_spans_and_reads_no_clock(tmp_path):
    """A caller that drives ``BatchedKVCacheDecoder`` itself (the
    benchmark's reference check, a test) gets the three driver spans
    under no scheduler phase and no duration: durations exist only on
    the clock a caller hands in."""
    sched = _scheduler("spans-driver", FakeClock())
    drv = sched.engine.driver(2)
    tokens, idx = np.zeros((2, 1), np.int32), np.zeros(2, np.int32)

    def body():
        drv.select_rows(drv.step(tokens), idx)

    events = _profiled(tmp_path, body)
    for name in DRIVER_SPANS:
        assert len(_named(events, name)) == 1, name
    assert not _named(events, "serve.decode.iter.dispatch")
    assert drv.last_stage is None and drv.last_launch is None \
        and drv.last_select is None
    reads = []

    def now():
        reads.append(len(reads))
        return float(len(reads))

    drv.select_rows(drv.step(tokens, now=now), idx, now=now)
    assert (drv.last_stage, drv.last_launch, drv.last_select) == (1, 1, 1)
    assert len(reads) == 5
    drv.step(tokens)
    assert drv.last_stage is None and drv.last_launch is None


# name -> events a 3-batch epoch leaves: the producer's and the loop's
# last probe runs into StopIteration inside batch/fetch/data_wait
FIT_NAMES = {"io.prefetch.batch": STEPS + 1, "io.prefetch.fetch": STEPS + 1,
             "io.prefetch.to_device": STEPS, "io.prefetch.put": STEPS,
             "module.fit.data_wait": STEPS + 1,
             "module.fit.update_metric": STEPS,
             "module.fit.callback": STEPS}


@pytest.mark.parametrize("name", sorted(FIT_NAMES))
def test_fit_and_producer_names_once_per_batch(fit_trace, name):
    assert len(_named(fit_trace, name)) == FIT_NAMES[name]
    if name.startswith("io.prefetch.") and name != "io.prefetch.batch":
        _assert_inside(fit_trace, name, "io.prefetch.batch")


def test_producer_and_loop_are_two_threads(fit_trace):
    producer = {e[0] for e in _named(fit_trace, "io.prefetch.batch")}
    loop = {e[0] for e in _named(fit_trace, "module.fit.data_wait")}
    assert len(producer) == 1 and len(loop) == 1 and producer != loop
    # the existing sites ride the same bridge without an edit
    for name in ("module.fit.batch", "io.load_batch", "io.next"):
        assert _named(fit_trace, name), name


@pytest.mark.parametrize("clock", ["fake", "real"])
def test_ring_record_phase_fields(clock):
    """The always-on ring record splits ``step_us`` (first clock read ->
    logits on the host) into plan + dispatch + fetch and adds commit and
    rewind, all on the scheduler's clock seam: zero under FakeClock."""
    flightrec.configure(capacity=4096)
    flightrec.clear()
    sched = _scheduler("spans-ring-" + clock,
                       FakeClock() if clock == "fake" else MonotonicClock())
    sched.submit(np.arange(1, 10), max_new_tokens=4)
    sched.pump()
    recs = [r for r in flightrec.get_records()
            if r["kind"] == "serve.decode.step"]
    assert [r["iter"] for r in recs] == list(range(len(recs)))
    assert len(recs) >= ITERS
    fields = ("plan_us", "dispatch_us", "fetch_us", "commit_us",
              "rewind_us")
    for r in recs:
        assert all(r[f] >= 0 for f in fields), r
        if clock == "fake":
            assert all(r[f] == 0 for f in fields) and r["step_us"] == 0
        else:
            parts = r["plan_us"] + r["dispatch_us"] + r["fetch_us"]
            # three truncations to whole microseconds
            assert abs(parts - r["step_us"]) <= max(3, r["step_us"] // 100)
        # ISSUE 36: the same boundaries as the new annotations
        parts = ("stage_us", "launch_us", "select_us", "ids_us", "lock_us",
                 "turn_us")
        assert all(r[f] >= 0 for f in parts), r
        assert r["stage_us"] + r["launch_us"] + r["select_us"] \
            <= r["dispatch_us"]
        assert r["ids_us"] <= r["fetch_us"]
        if clock == "fake":
            assert all(r[f] == 0 for f in parts)
    assert recs[0]["turn_us"] == 0      # no iteration ran before it
    stats = sched.stats()
    assert stats["compiles_since_warmup"] == 0
    assert stats["backend_compiles_since_warmup"] >= 0


class _TickClock(FakeClock):
    """2**-10 s (976 whole microseconds, and exact in binary) pass at
    every read: a duration counts the reads between its two."""

    def now(self):
        return self.advance(2.0 ** -10)


def test_ring_record_fields_count_the_clock_reads():
    """Under a clock that ticks once a read the ring record is the same
    in every run and says where the reads are: one inside each of
    stage, launch, select_rows, fetch.ids and the lock wait, six from
    the plan's end to the launches' (``dispatch_us``), three in
    ``fetch``, one from an iteration's rewind to the next one's arrival
    at the lock (``turn_us``). Fourteen reads an iteration, eight of
    them ISSUE 36's."""
    flightrec.configure(capacity=4096)
    flightrec.clear()
    clock = _TickClock()
    sched = _scheduler("spans-ring-tick", clock)
    sched.submit(np.arange(1, 10), max_new_tokens=4)
    before = clock.now()
    sched.pump()
    recs = [r for r in flightrec.get_records()
            if r["kind"] == "serve.decode.step"]
    assert len(recs) >= ITERS
    assert (clock.now() - before) * 2 ** 10 == 14 * len(recs) + 1
    for k, r in enumerate(recs):
        got = {f: r[f] for f in r if f.endswith("_us") and f != "ts_us"}
        assert got == {
            "lock_us": 976, "plan_us": 976, "stage_us": 976,
            "launch_us": 976, "select_us": 976, "dispatch_us": 5859,
            "ids_us": 976, "fetch_us": 2929, "step_us": 9765,
            "commit_us": 976, "rewind_us": 976,
            "turn_us": 976 if k else 0}, (k, got)


# ISSUE 46: the next S=1 step is launched before the last one's ids
# reach the host; ISSUE 53: so is the next window
AHEAD_ITERS = 5


@pytest.fixture(scope="module")
def ahead_trace(tmp_path_factory):
    """Five ``pump()`` iterations of the same request with nothing
    held back: the first window, which launches the second behind
    itself, and four iterations that each commit a dispatch launched an
    iteration ago - the second window, then S=1 steps - and launch
    another: the S=1 step that feeds the prompt's last token from the
    host, then steps fed from the chip."""
    tm.disable()
    sched = _scheduler("spans-ahead", MonotonicClock(), ahead=True)
    sched.submit(np.arange(1, 10), max_new_tokens=4)
    events = _profiled(tmp_path_factory.mktemp("ahead"),
                       lambda: sched.pump(max_iterations=AHEAD_ITERS))
    assert sched._ahead is not None
    sched.pump()
    return events


@pytest.mark.parametrize("name", [
    "serve.decode.iter." + p for p in DECODE_PHASES]
    + list(DRIVER_SPANS) + ["serve.decode.iter.fetch.ids", "executor.run"])
def test_ahead_spans_keep_their_names_and_their_parents(ahead_trace, name):
    """One plan, fetch, commit, rewind and account an iteration whatever
    the order; the launches are one a dispatch, so one more than the
    iterations while a dispatch is in flight."""
    launches = name.endswith(".dispatch") or name in DRIVER_SPANS \
        or name == "executor.run"
    assert len(_named(ahead_trace, "serve.decode.iter")) == AHEAD_ITERS
    assert len(_named(ahead_trace, name)) == AHEAD_ITERS + launches
    parent = "serve.decode.iter"
    if name in DRIVER_SPANS or name == "executor.run":
        parent = "serve.decode.iter.dispatch"
    elif name.endswith("fetch.ids"):
        parent = "serve.decode.iter.fetch"
    _assert_inside(ahead_trace, name, parent)


def test_a_step_is_launched_before_its_predecessors_ids_are_fetched(
        ahead_trace):
    """From the first window on, every iteration launches the next
    dispatch between its plan and its fetch."""
    iters = sorted((e[2], e[3]) for e in
                   _named(ahead_trace, "serve.decode.iter"))
    per_iter = []
    for lo, hi in iters:
        inside = sorted(
            (e[2], e[3], e[1].rsplit(".", 1)[-1]) for e in ahead_trace
            if e[1] in ("serve.decode.iter.plan",
                        "serve.decode.iter.dispatch",
                        "serve.decode.iter.fetch",
                        "serve.decode.iter.commit") and lo <= e[2] < hi)
        for (_a, end, _n), (start, _b, _m) in zip(inside, inside[1:]):
            assert end <= start
        per_iter.append([n for _a, _b, n in inside])
    assert per_iter == [
        ["plan", "dispatch", "dispatch", "fetch", "commit"]] + [
        ["plan", "dispatch", "fetch", "commit"]] * 4
    stats = [e[4] for e in sorted(_named(ahead_trace, "serve.decode.iter"),
                                  key=lambda e: e[2])]
    assert [int(st["window"]) for st in stats] == [S, S, 1, 1, 1]


def test_a_windows_launch_opens_before_its_predecessors_ids_close(
        ahead_trace):
    """ISSUE 53: the second window's ``decode.step.launch`` opens before
    the first one's ``serve.decode.iter.fetch.ids`` closes, as every
    later dispatch's does before its predecessor's: the chip has the
    next program while the host waits for the ids of the last."""
    launches = sorted((e[2], e[3]) for e in
                      _named(ahead_trace, "decode.step.launch"))
    ids = sorted((e[2], e[3]) for e in
                 _named(ahead_trace, "serve.decode.iter.fetch.ids"))
    assert len(launches) == AHEAD_ITERS + 1 and len(ids) == AHEAD_ITERS
    for k, (_opened, closed) in enumerate(ids):
        assert launches[k][1] <= closed          # its own, launched
        assert launches[k + 1][0] < closed       # and the one behind
    runs = sorted(_named(ahead_trace, "executor.run"), key=lambda e: e[2])
    assert [e[4]["kind"] for e in runs] == ["fwd_infer"] * (AHEAD_ITERS + 1)


def test_ring_record_of_a_dispatch_launched_ahead_counts_the_clock_reads():
    """Under the clock that ticks once a read: a dispatch launched
    ahead has its own launches in ``dispatch_us`` / ``stage_us`` /
    ``launch_us`` / ``select_us``, the time the host was blocked on its
    ids in ``fetch_us``, and in ``step_us`` the reads from its
    predecessor's ids on the host to its own - what one token costs a
    caller - whichever iteration they fall in."""
    flightrec.configure(capacity=4096)
    flightrec.clear()
    clock = _TickClock()
    sched = _scheduler("spans-ring-ahead", clock, ahead=True)
    sched.submit(np.arange(1, 10), max_new_tokens=4)
    before = clock.now()
    sched.pump()
    recs = [r for r in flightrec.get_records()
            if r["kind"] == "serve.decode.step"]
    assert [(r["window"], r["ahead"]) for r in recs] == [
        (S, 0), (S, 1), (1, 1), (1, 1), (1, 1), (1, 1)]
    tick = 2.0 ** -10 * 1e6
    # the first window launches the second behind itself: an
    # iteration's 14 reads and the launch's six more; an iteration that
    # commits a dispatch launched an iteration ago and launches another
    # reads 14, the last, which launches nothing, 8
    assert (clock.now() - before) * 2 ** 10 == 20 + 14 * 4 + 8 + 1
    for r in recs:
        assert r["dispatch_us"] == int(6 * tick) and \
            r["fetch_us"] == int(3 * tick) and r["ids_us"] == int(tick)
        assert r["stage_us"] == r["launch_us"] == r["select_us"] \
            == r["lock_us"] == r["commit_us"] == r["rewind_us"] == int(tick)
    # first clock read under the lock -> ids on the host, the launch of
    # the window behind included
    assert recs[0]["step_us"] == int(16 * tick)
    # from the predecessor's ids to its own, a window's like a step's:
    # that iteration's commit and rewind, then arrival, lock, plan, a
    # launch, the fetch
    assert [r["step_us"] for r in recs[1:]] == [int(14 * tick)] * 4 + [
        int(8 * tick)]
    # a plan section's time is charged to what it planned first, and
    # to the dispatch it commits where it planned nothing
    assert [r["plan_us"] for r in recs] == [
        int(tick), 0, int(tick), int(tick), int(tick), int(2 * tick)]


def test_span_without_jax_is_the_null_span():
    """A process that never imported jax (the launcher, a decode
    worker) gets the shared no-op, and telemetry does not import it."""
    code = (
        "import sys, types\n"
        "pkg = types.ModuleType('mxnet_tpu')\n"
        f"pkg.__path__ = [{os.path.join(ROOT, 'mxnet_tpu')!r}]\n"
        "sys.modules['mxnet_tpu'] = pkg\n"
        "from mxnet_tpu.telemetry import core\n"
        "s = core.span('launcher.phase', k=1)\n"
        "assert s is core.null_span, s\n"
        "with s as e:\n"
        "    assert e.set(x=1) is e\n"
        "run = core.wrap_dispatch(lambda x: x + 1, 'fwd_infer')\n"
        "assert run(1) == 2\n"
        "assert 'jax' not in sys.modules\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_enabled_span_opens_the_annotation_too(tmp_path):
    """``mx.profiler`` users (span buffer on) get both sinks."""
    tm.reset()
    tm.enable()

    def body():
        with tm.span("both.sinks", step=3) as s:
            s.set(extra=1)

    events = _profiled(tmp_path, body)
    tm.disable()
    (ev,) = _named(events, "both.sinks")
    assert int(ev[4]["step"]) == 3 and int(ev[4]["extra"]) == 1
    (span,) = [s for s in tm.get_spans() if s.name == "both.sinks"]
    assert span.args == {"step": 3, "extra": 1}
    tm.reset()


def test_compile_counter_sees_backend_compiles():
    """ROADMAP D11: ``xla.compile.count`` counts XLA backend compiles -
    exactly one for a new jitted function, none on its second call -
    and the ring names the function."""
    tm.reset()

    def spans_probe_fn(x):
        return x * 3 + 1

    fn = jax.jit(spans_probe_fn)
    x = jnp.arange(4.0)
    x.block_until_ready()

    def count():
        m = tm.get_metric("xla.compile.count")
        return m.value if m is not None else 0

    before, total = count(), tm.core.backend_compiles()
    fn(x).block_until_ready()
    assert count() - before == 1
    assert tm.core.backend_compiles() - total == 1
    fn(x).block_until_ready()
    assert count() - before == 1
    recs = [r for r in flightrec.get_records() if r["kind"] == "xla.compile"]
    assert recs[-1]["fun_name"] == "jit(spans_probe_fn)"
    assert recs[-1]["dur_us"] > 0
    assert tm.get_metric("xla.compile.seconds").value > 0
    tm.reset()
    assert tm.core.backend_compiles() - total == 1      # monotone


@pytest.mark.parametrize("kind", ["fwd_infer", "fused_step"])
def test_programs_are_named_by_kind_and_data_shape(kind):
    """The XLA module is ``jit_<kind>_<shape of the first input>``, so
    a trace's XLA Modules line tells the programs apart."""
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=3),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu(),
                        logger=logging.getLogger("spans_names"))
    if kind == "fwd_infer":
        mod.bind([("data", (5, 10))], None, for_training=False)
        mod.init_params()
        exe = mod._exec_group.executor
        assert exe.program_name(kind) == "fwd_infer_5x10"
        text = exe._get_program(kind).lower(
            exe._arg_vals(), exe._aux_vals(),
            jax.random.PRNGKey(0)).as_text()
        assert "module @jit_fwd_infer_5x10" in text
        return
    flightrec.configure(capacity=4096)
    flightrec.clear()
    X = np.random.rand(12, 10).astype("f")
    Y = (np.random.rand(12) * 3).astype("f")
    mod.fit(mx.io.NDArrayIter(X, Y, batch_size=6), num_epoch=1,
            optimizer_params={"learning_rate": 0.1})
    assert mod._fused_armed
    compiled = [r["fun_name"] for r in flightrec.get_records()
                if r["kind"] == "xla.compile"]
    assert "jit(fused_step_6x10)" in compiled
