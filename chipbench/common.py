"""What both runners share: where the caches live, the device check,
the compile watch, the profiler switch and the result line."""
from __future__ import annotations

import json
import math
import os
import shutil
import sys
import time

from .manifest import ROOT

CACHE_DIR = os.path.join(ROOT, ".chipbench_cache")


def say(line, **fields):
    """One JSON object on a line of its own; the LAST line of a run is
    the result, every earlier one is for the reader."""
    print(json.dumps({"chipbench": line, **fields}), flush=True)


def set_caches():
    """Before jax or mxnet_tpu is imported. The kernel tier's decisions
    go to a fixed directory inside the checkout, so that every run after
    a cell's first builds the same programs and finds them in the
    compile cache - the warm-restart setting of a deployment
    (MXNET_AUTOTUNE_CACHE_DIR, docs/env_var.md). The compile cache goes
    where the machine says, else to the program's own default
    (<checkout>/.jax_cache); it keeps every program, however quickly it
    compiled."""
    os.environ["MXNET_AUTOTUNE_CACHE_DIR"] = os.path.join(CACHE_DIR,
                                                          "autotune")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    # a crash report, should the program write one, stays in the checkout
    os.environ.setdefault("MXNET_CRASH_DIR", os.path.join(CACHE_DIR, "crash"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def require_devices(chips, rehearse=False):
    """``{"platform", "kind", "count"}`` of the cell's devices, or exit
    non-zero naming what jax found. Never a CPU number under a device
    metric's name: ``rehearse`` (CPU, tiny sizes, by hand) marks every
    line it prints."""
    import jax
    devs = jax.devices()
    found = [f"{d.platform}:{d.device_kind}" for d in devs]
    if not rehearse:
        if devs[0].platform != "tpu":
            raise SystemExit(f"chipbench: no accelerator - jax.devices() "
                             f"found {found}")
        if len(devs) < chips:
            raise SystemExit(f"chipbench: this cell needs {chips} chip(s), "
                             f"jax.devices() found {found}")
    elif len(devs) < chips:
        raise SystemExit(f"chipbench: rehearsal needs {chips} devices "
                         f"(XLA_FLAGS=--xla_force_host_platform_device_"
                         f"count={chips}), jax.devices() found {found}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips):
    """Peak bytes on the fullest of the cell's chips (0 where the
    backend reports none): the allocator's peak of buffers in use plus
    the peak it reserved for programs' own scratch space. On the v5e a
    program's temporaries (a train step's saved activations) are in the
    second figure only: ResNet-50 at batch 256 reads 1.15 GB in use and
    5.17 GB reserved."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def memory_stats():
    """The first device's allocator statistics as the backend reports
    them, for an earlier line."""
    import jax
    return {k: int(v) for k, v in
            (jax.devices()[0].memory_stats() or {}).items()}


class CompileWatch:
    """jax.monitoring's own compile events: one ``(time, name, seconds)``
    for each trace, lowering and backend compile (or cache read), and
    the compile cache's hit and miss counts."""

    def __init__(self):
        import jax.monitoring as mon
        self.events = []
        self.cache = {"hits": 0, "misses": 0}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            self.events.append((time.perf_counter(), event, duration,
                                kw.get("fun_name")))

    def _on_event(self, event, **_kw):
        if event.endswith("/cache_hits"):
            self.cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            self.cache["misses"] += 1

    def backend_compiles(self, t0=None, t1=None):
        """Backend compile events (an XLA compile or a cache read of a
        program new to this process) in [t0, t1)."""
        return [(fun, d) for t, name, d, fun in self.events
                if name.endswith("backend_compile_duration")
                and (t0 is None or t >= t0) and (t1 is None or t < t1)]

    def slowest(self, n=6):
        """The longest backend compile events (or cache reads):
        ``[function, seconds]``."""
        rows = sorted(self.backend_compiles(), key=lambda r: -r[1])[:n]
        return [[fun, round(d, 3)] for fun, d in rows]

    def seconds(self):
        return sum(e[2] for e in self.events)


def kernel_tier_table():
    """The kernel tier's decisions of this process, for an earlier
    line: a parent and a change that decided differently can be told
    apart."""
    from mxnet_tpu import kernel_tier
    rows = {}
    for d in kernel_tier.decisions():
        key = (d["op"], d.get("variant"), d.get("source"))
        rows[key] = rows.get(key, 0) + 1
    return [{"op": op, "variant": v, "source": s, "sites": n}
            for (op, v, s), n in sorted(rows.items(), key=str)]


class Tracer:
    """The JAX profiler around a few seconds of steady state. Host
    TraceMe events on, the Python tracer off (it slows the host it
    watches)."""

    def __init__(self, name):
        self.dir = os.path.join(CACHE_DIR, "trace", name)
        self.running = False
        self.events = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.running = True

    def stop(self):
        import jax
        from . import trace
        if not self.running:
            return
        jax.profiler.stop_trace()
        self.running = False
        self.events = trace.flatten(self.dir)
        dump = os.environ.get("CHIPBENCH_TRACE_DUMP")
        if dump:        # by hand: what the trace holds, for a reader
            os.makedirs(dump, exist_ok=True)
            with open(os.path.join(dump, os.path.basename(self.dir)
                                   + ".trace_summary.json"), "w") as f:
                json.dump(trace.summary(self.events), f, indent=1)
            with open(os.path.join(dump, os.path.basename(self.dir)
                                   + ".trace_slice.json"), "w") as f:
                json.dump(trace.head(self.events, 0.25), f)
        shutil.rmtree(self.dir, ignore_errors=True)


def autotuned_sites(table):
    """How many of ``kernel_tier_table()``'s sites were timed in this
    process (0 in a warm run)."""
    return sum(r["sites"] for r in table if r["source"] == "autotune")


def end_to_end_metrics(cell, values):
    """The ``--trace 0`` metrics: the cell's end-to-end metrics that
    have a value."""
    return {m.name: {"value": values[m.name], "unit": m.unit}
            for m in cell.end_to_end if values.get(m.name) is not None}


def per_layer_metrics(cell, obs):
    """The ``--trace 1`` metrics: each of the cell's per-layer metrics
    whose reader found something to read."""
    from . import readers
    out = {}
    for m in cell.per_layer:
        v = readers.read(m, obs)
        if v is not None:
            out[m.name] = {"value": v, "unit": m.unit}
    return out


def result_line(correct, attempted, failed, metrics, device, peak_bytes,
                tracer=None, compared=None):
    """The contract's last line. ``compared`` is every number that
    decided ``correct`` beside its limit, ``{name: (value, limit)}``
    (the value may not pass the limit; a name that ends in
    ``_at_least`` may not fall under it): the line's last key, and the
    last lines on standard error."""
    from . import trace
    device = dict(device, memory_peak_bytes=int(peak_bytes))
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if tracer is not None and tracer.events:
        busy_s, window_s = trace.busy(tracer.events)
        device["busy_s"], device["window_s"] = busy_s, window_s
        bd = trace.breakdown(tracer.events)
        if bd:
            out["breakdown"] = bd
    # a NaN or an infinity is no JSON: such a reading goes out by name
    out["compared"] = {
        name: {"value": v if math.isfinite(v) else repr(v), "limit": lim}
        for name, (v, lim) in (compared or {}).items()}
    sys.stdout.flush()
    for name, c in out["compared"].items():
        print(f"chipbench compared {name}: {c['value']} (limit "
              f"{c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
