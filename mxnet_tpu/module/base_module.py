"""BaseModule: the abstract train/evaluate/predict interface.

API parity with reference python/mxnet/module/base_module.py — ``fit``
runs bind -> init_params -> init_optimizer -> per-batch
forward_backward/update/update_metric with the same callback hook points
— reorganized here into small helpers (`_prepare_fit`, `_fit_epoch`)
around the single-executor design. Subclasses implement the narrow
abstract surface at the bottom.
"""
from __future__ import annotations

import logging
import os
import time

import numpy as np

from .. import metric as metric_mod
from .. import ndarray as nd
from .. import telemetry as _telemetry
from ..model import BatchEndParam

__all__ = ["BaseModule"]


def _as_list(obj):
    return obj if isinstance(obj, list) else [obj]


def _fire(callbacks, param):
    for cb in _as_list(callbacks):
        cb(param)


def _check_input_names(symbol, names, typename, throw):
    """Verify user-declared input names exist among the symbol's args."""
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        non_params = [a for a in args
                      if not a.split("_")[-1] in
                      ("weight", "bias", "gamma", "beta")]
        msg = (f"{typename} name {name!r} is not an argument of the symbol "
               f"(free inputs are: {non_params})")
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class BaseModule:
    """Shared high-level driver; subclasses provide the executor plumbing."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # ------------------------------------------------------------- training
    def forward_backward(self, data_batch):
        """One fused fwd+bwd pass (the hot call of fit)."""
        self.forward(data_batch, is_train=True)
        self.backward()

    def _prepare_fit(self, train_data, initializer, arg_params, aux_params,
                     allow_missing, force_rebind, force_init, kvstore,
                     optimizer, optimizer_params, monitor):
        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        self._mfu_profile = self._build_mfu_profile(train_data)

    def _build_mfu_profile(self, train_data):
        """(train FLOPs/batch, peak FLOP/s or None) from the op cost
        metadata + the optimizer update — the per-batch MFU gauge's
        numerator and denominator (telemetry/mfu.py). Best-effort:
        anything missing (no symbol, partial shapes) disables the gauge
        rather than guessing."""
        try:
            sym = getattr(self, "_symbol", None) or self.symbol
            if sym is None:
                return None
            shapes = {nm: tuple(s) for nm, s in
                      list(train_data.provide_data) +
                      list(train_data.provide_label or [])}
            table = _telemetry.mfu.cost_table(sym, shapes, train=True)
            flops = table["train_flops"]
            if not flops:
                return None
            opt = getattr(self, "_optimizer", None)
            if opt is not None:
                from ..ops.cost import optimizer_flops
                n_params = sum(
                    int(np.prod(a.shape)) for a in
                    (getattr(self, "_arg_params", None) or {}).values())
                flops += optimizer_flops(type(opt).__name__, n_params)
            peak, _bw = _telemetry.mfu.device_peaks()
            _telemetry.mfu.record_gauges(table, train=True)
            return flops, peak
        except Exception:
            return None

    def _scan_window_size(self):
        """Batches advanced per device dispatch by the fit loop; 1 means
        the plain per-batch loop. Module overrides this with the K-step
        scan-fused arrangement (module.fit steps_per_dispatch)."""
        return 1

    @staticmethod
    def _iter_with_data_wait(train_data):
        """Iterate ``train_data``, banking the time each ``next()``
        blocks (the PrefetchingIter handoff) into the step-attribution
        plane as the upcoming step's ``data_wait`` phase. One branch
        per batch when attribution is off."""
        it = iter(train_data)
        sa = _telemetry.stepattr
        while True:
            armed = sa.armed()
            t0 = sa.clock() if armed else 0.0
            try:
                with _telemetry.span("module.fit.data_wait"):
                    batch = next(it)
            except StopIteration:
                return
            if armed:
                sa.note_data_wait(sa.clock() - t0)
            yield batch

    def _fit_epoch(self, epoch, train_data, eval_metric, batch_end_callback,
                   monitor, skip=0):
        K = self._scan_window_size()
        if K > 1 and monitor is None:
            return self._fit_epoch_scan(epoch, train_data, eval_metric,
                                        batch_end_callback, K, skip=skip)
        sa = _telemetry.stepattr
        nbatch = -1
        for nbatch, batch in enumerate(
                self._iter_with_data_wait(train_data)):
            if nbatch < skip:
                # resume fast-forward: these batches already trained
                # before the kill; consuming them keeps the data stream
                # (and any restored shuffle rng) aligned with the
                # uninterrupted run
                sa.clear_pending_wait()
                continue
            if monitor is not None:
                monitor.tic()
            sa.step_begin(epoch, nbatch)
            batch_span = _telemetry.span(
                "module.fit.batch", _hist="module.fit.batch.seconds",
                epoch=epoch, nbatch=nbatch)
            t0 = time.perf_counter_ns()
            with batch_span:
                self.forward_backward(batch)
                self.update()
            if _telemetry.enabled():
                _telemetry.counter("module.fit.batches").inc()
                _telemetry.record_event(
                    "batch_end", epoch=epoch, nbatch=nbatch,
                    duration_us=batch_span.dur,
                    batch_size=getattr(train_data, "batch_size", 0))
                self._note_mfu(batch_span.dur)
            else:
                # the span tracer is off (the production default) — the
                # always-on flight ring still gets a batch timeline so a
                # crash report can show what the run was doing
                _telemetry.flightrec.note(
                    "module.fit.batch", epoch=epoch, nbatch=nbatch,
                    dur_us=(time.perf_counter_ns() - t0) // 1000,
                    batch_size=getattr(train_data, "batch_size", 0))
            self._health_tick(epoch, nbatch)
            with _telemetry.span("module.fit.update_metric"):
                self.update_metric(eval_metric, batch.label)
            sa.step_end()
            if monitor is not None:
                monitor.toc_print()
            if batch_end_callback is not None:
                with _telemetry.span("module.fit.callback"):
                    _fire(batch_end_callback,
                          BatchEndParam(epoch=epoch, nbatch=nbatch,
                                        eval_metric=eval_metric,
                                        locals=locals()))
            self._ckpt_tick(epoch, nbatch)
        # epoch end: release the one-boundary health-stat lag
        self._health_tick(epoch, nbatch + 1, steps=0, flush=True)

    def _fit_epoch_scan(self, epoch, train_data, eval_metric,
                        batch_end_callback, K, skip=0):
        """Windowed epoch: K batches per device dispatch via the scan-
        fused program. Metrics, telemetry and callbacks still advance
        per logical batch — the per-step counts/outputs come back
        stacked from the one dispatch. Partial tail windows (and any
        window the scan can't take) fall back to single fused steps.
        Checkpoints are cut at window boundaries only (a snapshot
        mid-window has no consistent cursor — the K steps retire as one
        dispatch), so a resume ``skip`` is normally a multiple of K;
        a residue (checkpoint cut at a tail single) fast-forwards
        through split singles."""
        from ..io import StackedDataBatch
        nbatch = 0
        to_skip = int(skip)
        batch_size = getattr(train_data, "batch_size", 0)
        sa = _telemetry.stepattr

        def run_single(batch):
            nonlocal nbatch, to_skip
            if to_skip > 0:
                to_skip -= 1
                nbatch += 1
                sa.clear_pending_wait()
                return
            sa.step_begin(epoch, nbatch)
            t0 = time.perf_counter_ns()
            batch_span = _telemetry.span(
                "module.fit.batch", _hist="module.fit.batch.seconds",
                epoch=epoch, nbatch=nbatch)
            with batch_span:
                self.forward_backward(batch)
                self.update()
            self._note_batch(epoch, nbatch, batch_span.dur or
                             (time.perf_counter_ns() - t0) // 1000,
                             batch_size)
            self._health_tick(epoch, nbatch)
            with _telemetry.span("module.fit.update_metric"):
                self.update_metric(eval_metric, batch.label)
            sa.step_end()
            if batch_end_callback is not None:
                with _telemetry.span("module.fit.callback"):
                    _fire(batch_end_callback,
                          BatchEndParam(epoch=epoch, nbatch=nbatch,
                                        eval_metric=eval_metric,
                                        locals=locals()))
            self._ckpt_tick(epoch, nbatch)
            nbatch += 1

        def run_window(window, steps):
            nonlocal nbatch, to_skip
            if to_skip >= steps:
                to_skip -= steps
                nbatch += steps
                sa.clear_pending_wait()
                return
            if to_skip > 0:
                # cursor inside this window: fast-forward the remainder
                # as split singles (resume replays them through the
                # single fused step — same numerics, docs/checkpoint.md)
                singles = window.split() if hasattr(window, "split") \
                    else list(window)
                for b in singles:
                    run_single(b)
                return
            sa.step_begin(epoch, nbatch)
            t0 = time.perf_counter_ns()
            win_span = _telemetry.span(
                "module.fit.window", _hist="module.fit.window.seconds",
                epoch=epoch, nbatch=nbatch, steps=steps)
            with win_span:
                self._run_scan_window(window)
            # stash this window's K-stacked health stats and drain the
            # previous window's (one-boundary lag: its device work is
            # done, so the read never stalls the async scan dispatch)
            self._health_tick(epoch, nbatch, steps)
            dur_us = win_span.dur or (time.perf_counter_ns() - t0) // 1000
            for _ in range(steps):
                labels = self._advance_scan_batch()
                self._note_batch(epoch, nbatch, dur_us // steps,
                                 batch_size)
                with _telemetry.span("module.fit.update_metric"):
                    self.update_metric(eval_metric, labels)
                if batch_end_callback is not None:
                    with _telemetry.span("module.fit.callback"):
                        _fire(batch_end_callback,
                              BatchEndParam(epoch=epoch, nbatch=nbatch,
                                            eval_metric=eval_metric,
                                            locals=locals()))
                nbatch += 1
            # one attribution record per window: phases divide over the
            # K logical batches it retired
            sa.step_end(steps=steps)
            # checkpoint/dead-node boundary once per retired window —
            # the only consistent cursor under scan dispatch
            self._ckpt_tick(epoch, nbatch - 1)

        pending = []
        for batch in self._iter_with_data_wait(train_data):
            if isinstance(batch, StackedDataBatch):
                if batch.steps == K:
                    run_window(batch, K)
                else:                       # partial tail window
                    for b in batch.split():
                        run_single(b)
            else:
                pending.append(batch)
                if len(pending) == K:
                    run_window(pending, K)
                    pending = []
        for b in pending:                   # partial tail window
            run_single(b)
        # epoch end: release the one-boundary health-stat lag
        self._health_tick(epoch, nbatch, steps=0, flush=True)

    def _note_mfu(self, dur_us):
        """Model-level MFU gauge per batch: attributed train FLOPs over
        measured batch time, against the device peak when one is known
        (telemetry/mfu.py). Achieved-FLOP/s records even without a peak
        (CPU runs still get a throughput-in-FLOPs signal)."""
        prof = getattr(self, "_mfu_profile", None)
        if not prof or not dur_us:
            return
        flops, peak = prof
        secs = dur_us / 1e6
        _telemetry.gauge("mfu.achieved_flops_per_sec").set(flops / secs)
        if peak:
            _telemetry.gauge("mfu.model").set((flops / secs) / peak)

    def _note_batch(self, epoch, nbatch, dur_us, batch_size):
        """Per-logical-batch telemetry shared by both fit loops."""
        if _telemetry.enabled():
            _telemetry.counter("module.fit.batches").inc()
            _telemetry.record_event(
                "batch_end", epoch=epoch, nbatch=nbatch,
                duration_us=dur_us, batch_size=batch_size)
            self._note_mfu(dur_us)
        else:
            _telemetry.flightrec.note(
                "module.fit.batch", epoch=epoch, nbatch=nbatch,
                dur_us=dur_us, batch_size=batch_size)

    def _health_tick(self, epoch, nbatch, steps=1, flush=False):
        """Batch/window-boundary hook of both fit loops: drain the
        in-program health stats (armed runs only) into the process
        HealthMonitor and run the triage ladder on any rule firings.

        Stats drain only once the device reports them finished
        (take_health's readiness gate — an eager read would serialize
        the host behind in-flight windows), so a window's observations
        may arrive several boundaries late, each carrying the cursor of
        the batches that produced it. The escalation cursor stays
        ``(epoch, nbatch + steps)`` — the batches behind it all ran, so
        a resume from an emergency commit is always safe. ``flush``
        drains the whole backlog — the epoch-end call, where the loop
        syncs anyway."""
        hp = _telemetry.health
        eg = getattr(self, "_exec_group", None)
        if eg is None or not hp.armed():
            return
        take = getattr(eg, "take_health", None)
        if take is None:
            return
        stats_list = take(cursor=(epoch, nbatch), flush=flush)
        if not stats_list:
            return
        for stats, ep, nb in stats_list:
            for f in hp.observe(stats, epoch=ep, nbatch=nb):
                hp.escalate(f["rule"], f["policy"], f["message"],
                            module=self, epoch=epoch,
                            nbatch=nbatch + steps)

    # --------------------------------------------- checkpointing / recovery
    def _ckpt_tick(self, epoch, nbatch):
        """Batch-boundary hook of both fit loops: checkpoint cadence +
        the safe point to act on a dead-peer flag. ``nbatch`` is the
        batch that just retired, so the saved cursor is
        ``(epoch, nbatch + 1)`` — the next batch a resume runs."""
        mgr = getattr(self, "_ckpt_manager", None)
        if mgr is not None:
            mgr.tick(self, epoch, nbatch + 1)
        dead = getattr(self, "_dead_nodes_pending", None)
        if dead:
            from ..checkpoint import DeadWorkerError
            self._dead_handled = True   # the wedged watchdog stands down
            if mgr is not None:
                # boundary detection: state is consistent — cut an
                # emergency checkpoint before abandoning the job so
                # resume loses zero batches
                try:
                    mgr.save(self, epoch, nbatch + 1, block=True)
                except Exception:
                    self.logger.exception(
                        "emergency checkpoint failed; resume will use "
                        "the last committed one")
            raise DeadWorkerError(dead, clean=True)

    def _arm_recovery(self, elastic):
        """Subscribe to the kvstore heartbeat layer's dead-node seam
        (elastic mode): the watcher thread only sets a flag, the
        training thread raises at its next batch boundary. A survivor
        can also be WEDGED — blocked inside a collective the dead peer
        will never join (gloo usually fails fast on the broken
        connection, but a collective already in flight at the death can
        hang) — in which case no batch boundary ever comes. With
        ``MXNET_CKPT_HANG_ACTION=reexec`` a grace watchdog handles that
        terminal state the way an elastic agent would: if the training
        thread hasn't acted on the flag within
        ``MXNET_CKPT_HANG_GRACE`` seconds, the process re-execs itself
        over the survivor cluster directly (resume comes from the last
        COMMITTED checkpoint; the wedged step is abandoned)."""
        import threading
        self._dead_nodes_pending = None
        self._dead_handled = False
        self._ckpt_elastic = bool(elastic)
        if not self._ckpt_elastic:
            return
        kv = getattr(self, "_kvstore", None)
        if kv is None or not hasattr(kv, "on_dead_node") or \
                kv.num_workers <= 1:
            return

        def flag(ranks):
            self._dead_nodes_pending = ranks
            if os.environ.get("MXNET_CKPT_HANG_ACTION", "none") == \
                    "reexec":
                grace = float(os.environ.get("MXNET_CKPT_HANG_GRACE",
                                             "60"))
                threading.Thread(target=self._wedged_watchdog,
                                 args=(ranks, grace), daemon=True,
                                 name="mxnet-wedged-watchdog").start()

        kv.on_dead_node(flag)

    def _wedged_watchdog(self, dead_ranks, grace):
        """Last-resort escape for a survivor stuck inside a broken
        collective: after ``grace`` seconds with the dead-peer flag
        unhandled, assume the training thread is wedged in C++ (no
        Python-level interrupt can reach it) and re-exec this process
        over the survivor cluster. State is dirty by definition —
        resume uses the last committed checkpoint."""
        time.sleep(grace)
        if getattr(self, "_dead_handled", False):
            return                  # the training thread got there
        from ..checkpoint import reexec_survivor
        # benign race by design: _dead_handled is a GIL-atomic bool
        # handshake (training thread sets it at a batch boundary, this
        # watchdog checks after the grace window); the worst overlap is
        # both sides acting, and re-exec is idempotent on a committed
        # checkpoint
        self._dead_handled = True  # mxlint: guarded-by(gil)
        _telemetry.counter("recovery.wedged").inc()
        _telemetry.flightrec.note("recovery.wedged",
                                  ranks=list(dead_ranks),
                                  grace_s=grace)
        self.logger.error(
            "dead worker(s) %s flagged %.0fs ago and the training "
            "thread never reached a batch boundary — assuming it is "
            "wedged in a broken collective; re-execing over the "
            "survivor cluster", list(dead_ranks), grace)
        mgr = getattr(self, "_ckpt_manager", None)
        if mgr is not None:
            try:
                mgr.close()         # land any queued commits first
            except Exception:
                pass
        kv = getattr(self, "_kvstore", None)
        if kv is not None:
            try:
                kv.close(abort=True)
            except Exception:
                pass
        reexec_survivor(dead_ranks)

    def _maybe_dead_worker(self, exc):
        """Convert a mid-batch failure into DeadWorkerError when a peer
        is in fact dead (elastic mode): the survivor's collective fails
        fast on the broken connection, but heartbeat staleness needs a
        horizon — poll the liveness layer briefly before deciding the
        failure was something else."""
        from ..checkpoint import DeadWorkerError
        if isinstance(exc, DeadWorkerError):
            return
        if not getattr(self, "_ckpt_elastic", False):
            return
        kv = getattr(self, "_kvstore", None)
        if kv is None or kv.num_workers <= 1 or \
                not hasattr(kv, "get_dead_nodes"):
            return
        dead = getattr(self, "_dead_nodes_pending", None)
        flagged = bool(dead)
        if not dead:
            horizon = float(os.environ.get("PS_HEARTBEAT_TIMEOUT", "100"))
            patience = float(os.environ.get("MXNET_CKPT_DEAD_PATIENCE",
                                            "") or min(horizon + 5, 30))
            deadline = time.time() + patience
            prev = None
            while time.time() < deadline:
                try:
                    seen = kv.get_dead_nodes()
                except Exception:
                    seen = []
                # require two consecutive agreeing observations: a
                # transient coordination-service blip must not get
                # promoted into a cluster re-form
                if seen and seen == prev:
                    dead = seen
                    break
                prev = seen or None
                time.sleep(0.5)
        if dead:
            self._dead_handled = True   # the wedged watchdog stands down
            if not flagged:
                # the watcher thread counts flag-path detections; this
                # is the collective-failure path it hasn't seen yet
                _telemetry.counter("recovery.events").inc()
            _telemetry.flightrec.note("recovery.dead_worker",
                                      ranks=list(dead), clean=False)
            raise DeadWorkerError(dead, clean=False) from exc

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, steps_per_dispatch=None, zero_stage=None,
            spmd=None, mesh=None, checkpoint=None, resume=None,
            elastic=None, remat=None, health=None):
        """The training loop (reference base_module.py:368-507 contract).

        ``steps_per_dispatch`` (default ``MXNET_STEPS_PER_DISPATCH``,
        else 1) batches K training steps into ONE device dispatch via a
        jitted ``lax.scan`` over the fused step — the Python loop, batch
        load and dict-shuffle then cost 1/K per batch (docs/
        performance.md). Metrics/callbacks still fire per batch.

        ``zero_stage`` (default ``MXNET_ZERO_STAGE``, else 0): 1 selects
        ZeRO stage-1 sharded optimizer updates on a multi-device
        binding — gradients reduce-scatter inside the fused program,
        each device updates its 1/N parameter shard with 1/N of the
        optimizer state, updated params all-gather back
        (docs/performance.md). Numerically identical to stage 0.

        ``spmd`` (default ``MXNET_SPMD``, else off): True binds the
        GSPMD arrangement — one jitted program over the named mesh
        (``mesh``: a ``parallel.MeshConfig``; default ``MXNET_MESH_*``
        env, else a 1-D data axis over the contexts), params sharded per
        ctx_group tags, the gradient all-reduce/reduce-scatter emitted
        by XLA from the sharding specs, kvstore optional (pass
        ``kvstore=None``; a local store is dropped automatically).
        Numerically equivalent to the kvstore path
        (docs/performance.md).

        ``checkpoint`` (default: a manager over ``MXNET_CKPT_DIR`` when
        that env var is set, else off): a
        ``checkpoint.CheckpointManager`` — or a directory string to
        build one — that snapshots full training state asynchronously
        at its ``every_n_batches`` cadence plus every epoch end, into
        versioned atomically-committed checkpoint directories
        (docs/checkpoint.md).

        ``resume`` (default off): True (use ``checkpoint``'s directory)
        or a checkpoint-directory string — restore the newest committed
        checkpoint (params, optimizer state + update counts, rng chain)
        and continue from its cursor: earlier epochs are skipped and
        the cursor epoch fast-forwards past already-trained batches, so
        the resumed run continues bit-for-bit where the killed one
        stopped. Under ``steps_per_dispatch`` K the cursor lies on a
        window boundary (checkpoints are cut between windows).

        ``elastic`` (default ``MXNET_CKPT_ELASTIC``): with a dist
        kvstore, subscribe to the heartbeat layer's dead-node seam and
        raise ``checkpoint.DeadWorkerError`` (after an emergency save
        at the next batch boundary) instead of hanging in a collective
        against a dead peer — the caller re-forms the job over the
        survivors (``checkpoint.reexec_survivor``) and resumes.

        ``remat`` (default ``MXNET_REMAT_POLICY``, else ``"none"``):
        rematerialization policy for the fused/K-step program —
        ``"dots"`` recomputes the elementwise chains between saved
        matmul/conv outputs during backward, ``"all"`` replays the
        whole forward — shrinking the step's saved-residual set so the
        HBM freed by ZeRO and the memory accountant buys the
        next-larger batch bucket (docs/performance.md). The policy
        keys the program cache and the kernel-tier autotune cache, and
        extends donation to the step's eval-only intermediates (rng
        chain, fully-refreshed aux).

        ``health`` (default ``MXNET_TRAIN_HEALTH``): True arms the
        training-health plane — the fused/K-step program computes grad/
        param norms, update-ratio, per-head loss and a non-finite flag
        in-program, a ``telemetry.health.HealthMonitor`` (pass one as
        the value to customize detectors) runs divergence rules over
        them at batch/window boundaries, and firings run the triage
        ladder (``warn``/``snapshot``/``checkpoint``/``raise`` —
        ``MXNET_TRAIN_HEALTH_POLICY``), with emergency commits through
        this fit's checkpoint manager (docs/telemetry.md). Arming keys
        the program cache and pins process-wide, like ``remat``.
        """
        from ..initializer import Uniform
        from ..checkpoint import CheckpointManager, DeadWorkerError
        if num_epoch is None:
            raise ValueError("fit() needs num_epoch")
        if steps_per_dispatch is None:
            steps_per_dispatch = int(
                os.environ.get("MXNET_STEPS_PER_DISPATCH", "1") or 1)
        self._steps_per_dispatch = max(1, int(steps_per_dispatch))
        if zero_stage is not None:
            self._zero_stage = int(zero_stage)
        if spmd is not None:
            self._spmd = bool(spmd)
        if mesh is not None:
            self._mesh_config = mesh
        if remat is not None:
            from .. import remat as _remat_mod
            # pin process-wide so the kernel-tier autotune key sees the
            # same policy token the program-cache key carries
            self._remat = _remat_mod.set_active(remat)
        if health is not None:
            # arm (or install a caller-built monitor into) the training-
            # health plane BEFORE the fused program is built below —
            # arming is part of the program-cache key
            if isinstance(health, _telemetry.health.HealthMonitor):
                _telemetry.health.install(health)
            elif isinstance(health, dict):
                _telemetry.health.configure(armed=True, **health)
            else:
                _telemetry.health.configure(armed=bool(health))

        # checkpointing arrangement: explicit kwarg > MXNET_CKPT_DIR env
        # (the env path only engages on modules with an executor group —
        # full-state capture needs one; an explicit kwarg raises loudly)
        mgr, mgr_owned = None, False
        if checkpoint is None and os.environ.get("MXNET_CKPT_DIR") \
                and hasattr(self, "_exec_group"):
            checkpoint = os.environ["MXNET_CKPT_DIR"]
        if checkpoint is not None:
            if isinstance(checkpoint, CheckpointManager):
                mgr = checkpoint
            else:
                mgr = CheckpointManager(str(checkpoint))
                mgr_owned = True
        self._ckpt_manager = mgr
        if elastic is None:
            elastic = os.environ.get("MXNET_CKPT_ELASTIC", "").lower() \
                in ("1", "true", "yes", "on")

        self._prepare_fit(train_data, initializer or Uniform(0.01),
                          arg_params, aux_params, allow_missing,
                          force_rebind, force_init, kvstore, optimizer,
                          optimizer_params, monitor)
        self._arm_recovery(elastic)

        # exact resume: restore the newest committed checkpoint into the
        # freshly prepared module, then continue from its cursor
        skip_batches = 0
        if resume:
            from ..checkpoint import restore_module
            if resume is True and mgr is None:
                raise ValueError("fit(resume=True) needs a checkpoint "
                                 "manager (checkpoint=... or "
                                 "MXNET_CKPT_DIR)")
            resume_dir = mgr.directory if resume is True else str(resume)
            cursor = restore_module(self, resume_dir)
            if cursor is not None and int(cursor["epoch"]) >= begin_epoch:
                begin_epoch = int(cursor["epoch"])
                skip_batches = int(cursor["nbatch"])

        eval_metric = metric_mod.create(eval_metric)
        validation_metric = validation_metric or eval_metric

        # a prefetching iterator stages into device memory off-thread:
        # tell it where this binding wants its batches (so that every
        # chip gets its rows from the host and _load_batch moves
        # nothing), and, scan-capable, to stack K batches per window
        K = self._scan_window_size()
        placement = self._input_placement()
        if hasattr(train_data, "stack_windows"):
            train_data.stack_windows(K, device=placement)
        if eval_data is not train_data and \
                hasattr(eval_data, "stack_windows"):
            eval_data.stack_windows(1, device=placement)

        # triage binding: checkpoint-level health/sentinel escalations
        # land their emergency commit through THIS fit's manager
        _telemetry.health.bind_triage(self)
        try:
            self._fit_epochs(train_data, eval_data, eval_metric,
                             validation_metric, epoch_end_callback,
                             batch_end_callback, eval_end_callback,
                             eval_batch_end_callback, begin_epoch,
                             num_epoch, monitor,
                             skip_batches=skip_batches)
            if mgr is not None:
                mgr.wait()          # the last checkpoint must be durable
        except DeadWorkerError:
            raise                   # recovery path: dump written already
        except Exception as exc:
            # a dead peer shows up as a failed collective mid-batch:
            # convert to the recovery signal before post-mortem
            self._maybe_dead_worker(exc)
            # leave a post-mortem on disk: ring timeline + metrics +
            # memory watermarks (telemetry.flightrec crash report)
            _telemetry.flightrec.on_crash(exc, where="module.fit")
            raise
        finally:
            _telemetry.health.release_triage()
            if mgr_owned:
                mgr.close()

    def _input_placement(self):
        """Where the bound executor group places a batch - what its
        ``_place(arr, "data")`` asks for: the context of a one-device
        binding, the mesh's data sharding, or under an SPMD plan its
        shape-aware ``data_sharding_for``. None without a group."""
        group = getattr(self, "_exec_group", None)
        if group is None:
            return None
        if group._spmd_plan is not None:
            return group._spmd_plan.data_sharding_for
        if group._mesh is not None:
            return group._data_sharding
        return group.contexts[0]

    def _fit_epochs(self, train_data, eval_data, eval_metric,
                    validation_metric, epoch_end_callback,
                    batch_end_callback, eval_end_callback,
                    eval_batch_end_callback, begin_epoch, num_epoch,
                    monitor, skip_batches=0):
        for epoch in range(begin_epoch, num_epoch):
            start = time.time()
            eval_metric.reset()
            skip = skip_batches if epoch == begin_epoch else 0
            with _telemetry.span("module.fit.epoch",
                                 _hist="module.fit.epoch.seconds",
                                 epoch=epoch):
                self._fit_epoch(epoch, train_data, eval_metric,
                                batch_end_callback, monitor, skip=skip)

            name_values = eval_metric.get_name_value()
            for name, val in name_values:
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            time_cost = time.time() - start
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch, time_cost)
            if _telemetry.enabled():
                _telemetry.record_event(
                    "epoch_end", epoch=epoch, time_cost_s=time_cost,
                    metrics={n: float(v) for n, v in name_values})

            # pull the trained params off-device once per epoch so callbacks
            # (checkpointing) see current values
            arg_now, aux_now = self.get_params()
            self.set_params(arg_now, aux_now)
            if epoch_end_callback is not None:
                for cb in _as_list(epoch_end_callback):
                    cb(epoch, self.symbol, arg_now, aux_now)

            mgr = getattr(self, "_ckpt_manager", None)
            if mgr is not None:
                # epoch-boundary checkpoint: cursor = start of the next
                # epoch (async; the writer thread owns the disk work)
                mgr.save(self, epoch + 1, 0)

            if eval_data:
                for name, val in self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback,
                        epoch=epoch):
                    self.logger.info("Epoch[%d] Validation-%s=%f", epoch,
                                     name, val)
            train_data.reset()

    # ------------------------------------------------------------ evaluation
    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Run inference over ``eval_data`` accumulating ``eval_metric``."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()

        nbatch = 0
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                nbatch -= 1
                break
            self.forward(batch, is_train=False)
            self.update_metric(eval_metric, batch.label)
            if batch_end_callback is not None:
                _fire(batch_end_callback,
                      BatchEndParam(epoch=epoch, nbatch=nbatch,
                                    eval_metric=eval_metric,
                                    locals=locals()))
        if score_end_callback:
            _fire(score_end_callback,
                  BatchEndParam(epoch=epoch, nbatch=nbatch + 1,
                                eval_metric=eval_metric, locals=locals()))
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """Yield (outputs-without-pad, batch index, batch) per batch."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(batch, is_train=False)
            valid = [o[:o.shape[0] - batch.pad] for o in self.get_outputs()]
            yield valid, nbatch, batch

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Collect forward outputs over the iterator.

        With ``merge_batches`` the per-batch output lists are concatenated
        along the batch axis (requires a constant output arity — bucketed
        graphs with varying outputs should pass merge_batches=False).
        """
        per_batch = [outs for outs, _, _ in
                     self.iter_predict(eval_data, num_batch, reset)]
        if not per_batch:
            return per_batch
        if not merge_batches:
            return per_batch
        arity = len(per_batch[0])
        if any(len(outs) != arity for outs in per_batch):
            raise ValueError("output arity varies across batches; "
                             "use merge_batches=False")
        merged = [nd.concatenate([outs[i] for outs in per_batch])
                  for i in range(arity)]
        if arity == 1 and not always_output_list:
            return merged[0]
        return merged

    # ---------------------------------------------------------- param access
    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        payload = {f"arg:{k}": v for k, v in arg_params.items()}
        payload.update({f"aux:{k}": v for k, v in aux_params.items()})
        nd.save(fname, payload)

    def load_params(self, fname):
        arg_params, aux_params = {}, {}
        for key, value in nd.load(fname).items():
            kind, _, name = key.partition(":")
            if kind == "arg":
                arg_params[name] = value
            elif kind == "aux":
                aux_params[name] = value
            else:
                raise ValueError(
                    f"{fname} is not a param file (bad key {key!r})")
        self.set_params(arg_params, aux_params)

    # ------------------------------------------------------ abstract surface
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()

    def install_monitor(self, mon):
        raise NotImplementedError()
