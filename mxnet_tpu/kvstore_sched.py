"""Ready-order bucket scheduler for gradient synchronization.

The reference overlaps gradient reduction with backward compute through
its dependency engine: each layer's ZPush is enqueued the moment that
layer's gradient write completes, so ps-lite traffic for late layers
rides under the remaining backward ops (reference: kvstore_dist.h
ZPush + engine PushAsync ordering, and the DDP bucket design of Li et
al., VLDB 2020). This module is the TPU-native analog for
``KVStoreDistSync``: gradients are *staged* as they are pushed (in
reverse execution order — the order backward produces them), packed
into flat buckets, and each bucket's all-reduce is DISPATCHED the
moment the bucket fills — riding JAX async dispatch, so the collective
queues behind the still-running backward program instead of waiting
for a host sync. Nothing blocks until ``flush()`` (driven by ``pull``
or any state read), at which point the reduced values are scattered
back and applied in dispatch order.

Priorities finally mean something: ``push(priority=...)`` orders the
staging queue (higher = dispatched earlier), so a caller pushing
gradients as backward readiness dictates gets buckets on the wire in
that order.

Telemetry: ``kvstore.overlap.seconds`` accumulates, per bucket, the
window between dispatch and the flush that consumed it — collective
time that ran hidden behind other work; ``kvstore.exposed.seconds``
accumulates the residual host wait at flush. Per-bucket dispatch/apply
records land in the flight-recorder ring, and ``bucket_log`` keeps the
most recent per-bucket timings (the exposed-comm fraction and the
max number of buckets in flight follow from it).
"""
from __future__ import annotations

import collections
import time

import jax.numpy as jnp

from . import telemetry as _telemetry

__all__ = ["BucketScheduler"]


class _Bucket:
    __slots__ = ("entries", "raw", "dtype", "nbytes", "reduced",
                 "dispatch_t", "seq")

    def __init__(self, dtype, seq):
        self.entries = []        # (key, ctx, jnp array) in staging order
        self.raw = []            # original pending entries (for re-queue)
        self.dtype = dtype
        self.nbytes = 0
        self.reduced = None      # lazy flat result once dispatched
        self.dispatch_t = None
        self.seq = seq


class BucketScheduler:
    """Stage -> bucket -> async dispatch -> ordered apply.

    Parameters
    ----------
    reduce_flat : callable(jnp 1-D array) -> jnp 1-D array
        The collective; must dispatch asynchronously (jax native).
    apply_fn : callable(key, ctx, reduced jnp array)
        Consumer of each key's reduced value, run at flush in dispatch
        order (the kvstore updater / store assignment).
    bucket_bytes_fn : callable() -> int
        Bucket capacity, read per staging round (env-tunable).
    """

    def __init__(self, reduce_flat, apply_fn, bucket_bytes_fn):
        self._reduce = reduce_flat
        self._apply = apply_fn
        self._bucket_bytes = bucket_bytes_fn
        self._pending = []            # (prio, arrival, key, ctx, arr)
        self._arrival = 0
        self._staged = set()          # keys pending or in flight, unapplied
        self._inflight = []           # dispatched buckets, dispatch order
        self._seq = 0
        # recent per-bucket timings for diagnostics
        self.bucket_log = collections.deque(maxlen=1024)
        # order-audit trail for the static collective-order checker
        # (analysis rules CO301/DA204): which push call staged which key
        # at which priority, grouped by flush window. One dict append
        # per stage — negligible against the collective it schedules.
        self.stage_log = collections.deque(maxlen=1024)
        self._push_seq = 0            # distinct push() calls (arrival epochs)
        self._window = 0              # flush windows completed

    # ------------------------------------------------------------- staging
    def note_push_call(self):
        """Mark the start of one caller-level push(): entries staged
        under different push calls arrive in grad-ready order, which the
        collective-order analysis must treat as nondeterministic across
        workers (entries within one call share the caller's key order)."""
        self._push_seq += 1

    def stage(self, key, ctx, arr, priority=0):
        """Queue one key's merged gradient; dispatches any bucket the
        staging completes. A re-push of a still-unapplied key first
        flushes (two pushes of one key are two logical reductions)."""
        if key in self._staged:
            self.flush()
        self._staged.add(key)
        self._pending.append((priority, self._arrival, key, ctx, arr))
        self._arrival += 1
        self.stage_log.append({"key": key, "prio": priority,
                               "push": self._push_seq,
                               "buf": id(arr), "window": self._window})
        self._cut_buckets(dispatch_partial=False)

    def _cut_buckets(self, dispatch_partial):
        """Walk the pending queue in priority order, packing same-dtype
        flat buckets up to capacity. Full buckets dispatch immediately;
        partial ones dispatch only when ``dispatch_partial`` (flush),
        otherwise their entries return to pending untouched."""
        if not self._pending:
            return
        cap = self._bucket_bytes()
        # higher priority first; stable on arrival so a caller pushing
        # in backward-ready order keeps that order within a priority
        self._pending.sort(key=lambda e: (-e[0], e[1]))
        open_buckets = {}             # dtype -> _Bucket
        leftover = []
        for entry in self._pending:
            _, _, key, ctx, arr = entry
            a = jnp.asarray(arr)
            sz = int(a.size) * a.dtype.itemsize
            b = open_buckets.get(a.dtype)
            if b is not None and b.nbytes + sz > cap:
                self._dispatch(b)
                b = None
            if b is None:
                b = open_buckets[a.dtype] = _Bucket(a.dtype, self._seq)
                self._seq += 1
            b.entries.append((key, ctx, a))
            b.raw.append(entry)
            b.nbytes += sz
            if b.nbytes >= cap:
                self._dispatch(b)
                del open_buckets[a.dtype]
        for b in open_buckets.values():
            if dispatch_partial:
                self._dispatch(b)
            else:
                leftover.extend(b.raw)
        self._pending = leftover

    def _dispatch(self, bucket):
        """One async collective for the bucket's concatenated payload."""
        arrs = [jnp.ravel(a) for _, _, a in bucket.entries]
        flat = arrs[0] if len(arrs) == 1 else jnp.concatenate(arrs)
        bucket.reduced = self._reduce(flat)
        bucket.dispatch_t = time.perf_counter()
        if _telemetry.enabled():
            _telemetry.counter("kvstore.bucket.dispatched").inc()
            _telemetry.counter("kvstore.allreduce.bytes").inc(bucket.nbytes)
        _telemetry.flightrec.note(
            "kvstore.bucket.dispatch", seq=bucket.seq,
            keys=len(bucket.entries), bytes=bucket.nbytes)
        self._inflight.append(bucket)

    # --------------------------------------------------------------- flush
    def in_flight(self):
        """Dispatched-but-unapplied bucket count (diagnostics)."""
        return len(self._inflight)

    def drop_pending(self):
        """Discard everything staged or in flight WITHOUT applying it —
        the abort teardown (kvstore.close(abort=True)) for a store whose
        collective is already broken by a dead peer: a flush would
        re-enter the failed all-reduce, and the gradients of the batch
        being abandoned are no longer wanted anyway. Returns the number
        of entries dropped."""
        n = len(self._pending) + sum(len(b.entries)
                                     for b in self._inflight)
        self._pending = []
        self._inflight = []
        self._staged.clear()
        self._window += 1
        return n

    def flush(self):
        """Dispatch what remains pending, then apply every in-flight
        bucket's reduced values in dispatch order."""
        self._cut_buckets(dispatch_partial=True)
        self._window += 1       # close the audit window for stage_log
        if not self._inflight:
            self._staged.clear()
            return
        t_flush = time.perf_counter()
        telemetry_on = _telemetry.enabled()
        for b in self._inflight:
            t0 = time.perf_counter()
            red = b.reduced
            try:
                red.block_until_ready()
            except AttributeError:
                pass                      # non-jax stub in tests
            t1 = time.perf_counter()
            hidden = max(0.0, t_flush - b.dispatch_t)
            exposed = t1 - t0
            if telemetry_on:
                _telemetry.counter("kvstore.overlap.seconds").inc(hidden)
                _telemetry.counter("kvstore.exposed.seconds").inc(exposed)
            _telemetry.flightrec.note(
                "kvstore.bucket.apply", seq=b.seq, keys=len(b.entries),
                hidden_us=int(hidden * 1e6), exposed_us=int(exposed * 1e6))
            self.bucket_log.append({
                "seq": b.seq, "keys": len(b.entries), "bytes": b.nbytes,
                "key_ids": [k for k, _, _ in b.entries],
                "dispatch_t": b.dispatch_t, "apply_t": t1,
                "hidden_s": hidden, "exposed_s": exposed})
            off = 0
            for key, ctx, a in b.entries:
                n = int(a.size)
                self._apply(key, ctx, red[off:off + n].reshape(a.shape))
                off += n
        self._inflight = []
        self._staged.clear()
