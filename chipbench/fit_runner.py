"""Runner for configurations of kind "fit": ``Module.fit`` through
``examples/common/fit.py:fit``, as ``examples/train_imagenet.py`` calls
it, for a window of steady steps. What is trained - the symbol, its
data, its plain reference with its tolerance, its costs - is the
configuration's architecture (``archs/<arch>.py``; README.md, "The
architecture interface"). Here are the entry point and its window.

The one epoch never ends inside the window: a pool of host batches is
replayed through ``io.ResizeIter`` and (inside ``fit.fit``)
``io.PrefetchingIter`` until the window has closed. The window opens in
the ``batch_end_callback`` of the last lead-in step and closes in the
first callback past ``--seconds``, each time after ``block_until_ready``
on that step's outputs, so all the work of the counted steps lies
inside it.
"""
from __future__ import annotations

import argparse
import gc
import os
import sys
import time

import numpy as np

from . import common, manifest


def _loss(prob, label):
    prob = np.asarray(prob, np.float64)
    picked = prob[np.arange(len(label)), np.asarray(label, np.int64)]
    return float(-np.mean(np.log(np.maximum(picked, 1e-30))))


class _Window:
    """The ``batch_end_callback``: opens and closes the window, counts
    its steps, and in a traced run drives the profiler."""

    def __init__(self, seconds, lead_in, stop, tracer, trace_seconds):
        self.seconds, self.lead_in, self.stop = seconds, lead_in, stop
        self.tracer, self.trace_seconds = tracer, trace_seconds
        self.calls = 0
        self.t_open = self.t_close = None
        self.steps = 0
        self.first_loss = self.last_loss = None
        self.trace_at = None

    @staticmethod
    def _outputs(param):
        return param.locals["self"].get_outputs()[0]

    def _read_loss(self, param):
        label = param.locals["batch"].label[0].asnumpy()
        return _loss(self._outputs(param).asnumpy(), label)

    def __call__(self, param):
        self.calls += 1
        if self.t_close is not None:
            return
        if self.calls == 1:
            self.first_loss = self._read_loss(param)
        if self.t_open is None:
            if self.calls >= self.lead_in:
                self._outputs(param).asjax().block_until_ready()
                if self.tracer is not None:
                    from mxnet_tpu.telemetry import stepattr
                    stepattr.reset()
                    stepattr.configure(armed=True)
                gc.collect()
                gc.freeze()
                self.t_open = time.perf_counter()
                self.trace_at = self.t_open + min(1.0, self.seconds / 4)
            return
        self.steps += 1
        now = time.perf_counter()
        if self.tracer is not None:
            if not self.tracer.running and self.tracer.events is None \
                    and now >= self.trace_at:
                self.tracer.start()
                self.trace_until = now + min(self.trace_seconds,
                                             self.seconds / 2)
            elif self.tracer.running and now >= self.trace_until:
                self._outputs(param).asjax().block_until_ready()
                self.tracer.stop()
        if now - self.t_open >= self.seconds:
            self._outputs(param).asjax().block_until_ready()
            self.t_close = time.perf_counter()
            self.last_loss = self._read_loss(param)
            self.stop()


def _replay_iter(mx, data, labels, batch):
    """The pool as an endless epoch: NDArrayIter (host arrays, one
    host-to-device copy a batch) under ResizeIter, ended by ``stop()``."""

    class Replay(mx.io.ResizeIter):
        stopped = False

        def iter_next(self):
            return not self.stopped and super().iter_next()

    inner = mx.io.NDArrayIter(data, labels, batch, shuffle=False)
    return Replay(inner, size=1 << 40)


def check_reference(mx, mod, cfg, x, y, chips, arch):
    """One more step of the same fused program on a batch the run has
    not trained on (a fresh pool, so the outputs are not saturated),
    from a copy of the parameters taken before it: the program's loss
    (from the step's softmax output) against the architecture's plain
    float32 reference's loss on the same parameters and batch, within
    its ``LOSS_TOL``: |program - reference| <= TOL * max(1,
    |reference|). Returns ``(ok, report)``."""
    import jax
    import jax.numpy as jnp
    args, _aux = mod.get_params()
    # the fused step donates its parameter buffers: real copies
    params = {k: jnp.array(v.asjax(), copy=True) for k, v in args.items()}
    mod.forward_backward(mx.io.DataBatch(data=[mx.nd.array(x)],
                                         label=[mx.nd.array(y)]))
    mod.update()
    got = _loss(mod.get_outputs()[0].asnumpy(), y)
    after, _ = mod.get_params()
    watched = arch.UPDATED_PARAM
    changed = not np.array_equal(np.asarray(params[watched]),
                                 after[watched].asnumpy())
    devs = jax.devices()[:chips]
    mesh = jax.sharding.Mesh(np.array(devs), ("data",))
    rows = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    whole = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    params = jax.device_put(params, whole)
    xs, ys = jax.device_put(x, rows), jax.device_put(y, rows)
    # data-parallel BatchNorm takes its statistics over the global batch
    # (one GSPMD program) or over each chip's share (the reference
    # framework's per-device executors): the plain reference computes
    # both, the nearer one is compared and named
    want = {"global_batch": float(jax.jit(
        lambda p, a, b: arch.reference_loss(p, a, b, cfg))(params, xs, ys))}
    if chips > 1:
        def per_chip(p, a, b):
            a = a.reshape(chips, -1, *a.shape[1:])
            b = b.reshape(chips, -1)
            return jnp.mean(jax.vmap(
                lambda ai, bi: arch.reference_loss(p, ai, bi, cfg))(a, b))
        want["per_chip"] = float(jax.jit(per_chip)(params, xs, ys))
    stats, ref = min(want.items(), key=lambda kv: abs(kv[1] - got))
    ok = bool(np.isfinite(got) and abs(got - ref)
              <= arch.LOSS_TOL * max(1.0, abs(ref)))
    return ok and changed, {"program_loss": got, "reference_loss": ref,
                            "abs_diff": abs(got - ref),
                            "tolerance": arch.LOSS_TOL,
                            "batchnorm_statistics": stats,
                            "reference_losses": want,
                            "parameter_changed": changed}


def run(cell, seed, seconds, trace, device, t_start, rehearse=False):
    watch = common.CompileWatch()
    import mxnet_tpu as mx
    for path in (os.path.join(manifest.ROOT, "examples"), manifest.ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    from common import fit as fit_mod       # examples/common/fit.py
    from mxnet_tpu.telemetry import stepattr
    arch = manifest.load_arch(cell)

    cfg, mix = cell.config, cell.traffic
    phases = {"import_s": time.perf_counter() - t_start}
    batch = int(mix["global_batch"])
    if batch != cfg["batch_per_chip"] * cell.chips:
        raise SystemExit("chipbench: the traffic's global_batch is not "
                         "batch_per_chip x chips")
    seed32 = int(seed) % (1 << 31)
    mx.random.seed(seed32)
    np.random.seed(seed32)

    # exactly what the example's script hands to fit.fit: its arguments
    # (its own flags, fit.add_fit_args, the configuration's argv) and
    # its network
    parser = argparse.ArgumentParser()
    for flag, typ in arch.PARSER_FLAGS:
        parser.add_argument(flag, type=typ)
    fit_mod.add_fit_args(parser)
    argv = list(cfg["argv"]) + ["--batch-size", str(batch)]
    if rehearse:
        argv += ["--num-devices", str(cell.chips)]
    else:
        argv += ["--gpus", mix["gpus"]]
    args = parser.parse_args(argv)
    network = arch.symbol(cfg)

    t = time.perf_counter()
    data, labels = arch.pool(int(mix["pool_batches"]) * batch, cfg, seed)
    train = _replay_iter(mx, data, labels, batch)
    phases["data_s"] = time.perf_counter() - t

    tracer = common.Tracer(cell.name) if trace else None

    def stop():
        train.stopped = True

    win = _Window(seconds, int(mix["lead_in_steps"]), stop, tracer,
                  float(mix.get("trace_seconds", 3)))
    t = time.perf_counter()
    mod = fit_mod.fit(args, network, (train, None), batch_end_callback=win)
    stepattr.configure(armed=None)
    if tracer is not None and tracer.running:
        tracer.stop()
    if win.t_close is None:
        raise SystemExit("chipbench: fit returned before the window closed")
    setup_s = win.t_open - t_start
    phases["bind_compile_lead_in_s"] = win.t_open - t
    compiles_in_window = watch.backend_compiles(win.t_open, win.t_close)
    rate = win.steps * batch / (win.t_close - win.t_open)
    values = {"train_samples_per_s": rate, "setup_s": setup_s}

    tier = common.kernel_tier_table()
    common.say("kernel_tier", decisions=tier, compile_cache=dict(watch.cache))
    common.say("window", seconds=win.t_close - win.t_open, steps=win.steps,
               samples=win.steps * batch, first_loss=win.first_loss,
               last_loss=win.last_loss,
               compiles_in_window=compiles_in_window[:8],
               fused_armed=bool(getattr(mod, "_fused_armed", False)),
               end_to_end=values, **device, rehearsal=rehearse)
    common.say("setup", setup_s=setup_s, **phases,
               compile_events_s=watch.seconds(),
               memory_stats=common.memory_stats(),
               slow_compiles=watch.slowest(),
               compile_cache=dict(watch.cache),
               autotuned=common.autotuned_sites(tier))

    peak = common.memory_peak_bytes(cell.chips)   # before the reference's
    ok_ref, report = check_reference(
        mx, mod, cfg, *arch.pool(batch, cfg, seed + 1), cell.chips, arch)
    common.say("reference", ok=ok_ref, **report)
    finite = bool(np.isfinite(win.first_loss) and np.isfinite(win.last_loss))
    correct = ok_ref and finite and not compiles_in_window and win.steps > 0

    if trace:
        recs = [r for r in stepattr.records()
                if win.t_open * 1e6 <= r["ts_us"] < win.t_close * 1e6]
        obs = {"stepattr": recs, "events": tracer.events,
               "device_kind": device["kind"], "chips": cell.chips,
               "cost": arch.costs(cfg, batch)}
        common.say("traced", stepattr_records=len(recs),
                   events=len(tracer.events or []))
        metrics = common.per_layer_metrics(cell, obs)
    else:
        metrics = common.end_to_end_metrics(cell, values)
    compared = {
        "loss_abs_diff": (report["abs_diff"], report["tolerance"]
                          * max(1.0, abs(report["reference_loss"]))),
        "parameter_unchanged": (int(not report["parameter_changed"]), 0),
        "loss_not_finite": (int(not finite), 0),
        "compiles_in_window": (len(compiles_in_window), 0),
        "steps_in_window_at_least": (win.steps, 1)}
    common.result_line(correct, win.steps, 0 if finite else win.steps,
                       metrics, device, peak, tracer=tracer,
                       compared=compared)
