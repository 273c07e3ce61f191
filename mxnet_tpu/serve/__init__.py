"""Continuous-batching inference serving on the program cache.

Everything PRs 1–7 built for training amortization — the process-wide
program cache, persistent XLA cache, bucketed-shape modules, telemetry
and the flight recorder — is the hard half of a serving engine; this
package is the other half. In the style of Orca's iteration-level
scheduling and Clipper's deadline-aware adaptive batching:

* ``BucketEngine`` / ``PredictorEngine`` (engine.py) — pre-compiled
  forward programs over a configurable bucket ladder, warmed at startup
  through the program cache (and pinned there) so steady-state serving
  never compiles;
* ``AdmissionQueue`` + pad/slice helpers (batching.py) — coalesce
  requests into dynamic batches, pad to the nearest bucket, slice
  padded outputs back to per-request results;
* ``ModelRegistry`` (registry.py) — several models multi-tenant off one
  device pool, per-model ladders, deadline-aware fair scheduling;
* ``InferenceServer`` (server.py) — the in-process front end:
  ``serve(model).submit(inputs)`` returns a thread-safe sync+async
  ``ResponseHandle``; a dispatch thread (or an explicit deterministic
  ``pump()``) drives the scheduler;
* ``run_scripted`` (loadgen.py) — deterministic replay of scripted
  arrivals against a FakeClock-driven server.

Metrics (docs/serving.md has the catalog): ``serve.request.latency.
seconds`` histograms, ``serve.queue.depth`` / ``serve.batch.occupancy``
/ ``serve.padding.waste`` gauges, all exported by telemetry.prometheus,
plus a flight-ring record per dispatch.

Config: ``MXNET_SERVE_BUCKETS`` (default bucket ladder),
``MXNET_SERVE_MAX_QUEUE`` (admission bound), ``MXNET_SERVE_DEADLINE_MS``
(default request deadline) — docs/env_var.md.
"""
from __future__ import annotations

from ..faults import CircuitOpenError
from .clock import MonotonicClock, FakeClock
from .batching import (BucketLadder, QueueFullError, ResponseHandle,
                       ShedError, bucket_for, default_ladder, pad_rows,
                       slice_rows)
from .engine import BucketEngine, PredictorEngine
from .registry import ModelRegistry
from .server import InferenceServer, serve
from .warm import restore_server, save_server, server_payload
from .loadgen import run_scripted
from .decode import (DecodeEngine, DecodeHandle, DecodeScheduler,
                     default_prefill_chunk, default_slot_ladder,
                     default_spec_k, serve_decoder)
from .prefix import PrefixStore, default_prefix_budget_bytes
from .sampling import SamplingParams

__all__ = ["MonotonicClock", "FakeClock", "BucketLadder",
           "QueueFullError", "ShedError", "CircuitOpenError",
           "ResponseHandle", "bucket_for",
           "default_ladder", "pad_rows", "slice_rows", "BucketEngine",
           "PredictorEngine", "ModelRegistry", "InferenceServer",
           "serve", "restore_server", "save_server", "server_payload",
           "run_scripted", "DecodeEngine",
           "DecodeScheduler", "DecodeHandle", "default_slot_ladder",
           "default_prefill_chunk", "default_spec_k", "PrefixStore",
           "default_prefix_budget_bytes", "SamplingParams",
           "serve_decoder"]
