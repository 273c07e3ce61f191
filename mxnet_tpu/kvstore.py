"""KVStore: parameter synchronization.

Reference architecture (reference: src/kvstore/): ``local``/``device`` reduce
gradients across local GPUs through the engine (comm.h), ``dist_*`` go
through a ZMQ parameter server (ps-lite, kvstore_dist.h). The *API* —
init/push/pull/set_optimizer/rank/num_workers/barrier — is the compatibility
surface (SURVEY.md §5.8).

TPU-native design: there is no parameter server. Within a host, "reduce"
is a jnp sum (one fused XLA op across device copies); across hosts,
``dist_sync`` semantics are an all-reduce over the JAX distributed runtime
(ICI/DCN collectives) — the server vanishes, rank = ``jax.process_index()``.
``dist_async`` has no collective analog and is documented unsupported
(SURVEY.md §7 hard parts); creating it raises with that explanation.

Note the actual data-parallel hot path in this framework does NOT round-trip
gradients through KVStore handles: Module binds ONE sharded executor and XLA
inserts the psum (see module/executor_group.py). KVStore remains for API
parity, for the update_on_kvstore path, and for multi-host grad sync.
"""
from __future__ import annotations

import logging
import os
import pickle
import threading

import numpy as np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .ndarray import NDArray, zeros
from . import faults as _faults
from . import optimizer as opt
from . import telemetry as _telemetry
from .kvstore_sched import BucketScheduler

__all__ = ["KVStore", "create", "init_distributed"]


def _payload_bytes(vals):
    """Total bytes across a normalized list-of-list-of-NDArray payload."""
    n = 0
    for vlist in vals:
        for v in vlist:
            n += int(v.size) * np.dtype(v.dtype).itemsize
    return n


def _dist_initialized():
    return jax.distributed.is_initialized()


def init_distributed():
    """Connect this process to the training job's coordination service.

    The reference bootstraps its PS cluster from DMLC_* env vars set by
    tools/launch.py (reference: launch.py:33-75, MXInitPSEnv c_api.h:1196).
    The same env contract drives the TPU-native runtime: there are no
    server processes — DMLC_PS_ROOT_URI/PORT name the jax.distributed
    coordinator (hosted by worker 0) and every worker is a peer in the
    collective. Idempotent; a single-process run is a no-op.
    """
    n = int(os.environ.get("DMLC_NUM_WORKER", "1"))
    if n <= 1:
        return
    if _dist_initialized():
        return                               # already connected
    rank = int(os.environ.get("DMLC_WORKER_ID", "0"))
    uri = os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1")
    port = os.environ.get("DMLC_PS_ROOT_PORT", "9091")
    if jax.config.jax_platforms == "cpu" or \
            os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # multi-process CPU collectives need the gloo transport; must be
        # configured before the backend initializes
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # ps-lite reads PS_HEARTBEAT_TIMEOUT (seconds) for its failure
    # detector (reference: ps-lite/src/van.cc heartbeat handling); honor
    # the same knob for the coordination service's liveness tracking.
    heartbeat = int(os.environ.get("PS_HEARTBEAT_TIMEOUT", "100"))
    # Failure-handling mode. Default (fail-fast): JAX's error-polling
    # thread terminates every survivor the moment a peer misses its
    # heartbeat — the NCCL-abort analog, right for fit-and-restart jobs.
    # MXNET_KVSTORE_RECOVERABLE=1 selects ps-lite semantics instead: a
    # peer death is *reported* (get_num_dead_node, reference
    # kvstore_dist.h GetDeadNodes) and survivors keep running so they can
    # checkpoint/re-form; without the flag the fatal propagation would
    # make get_num_dead_node unobservable.
    if os.environ.get("MXNET_KVSTORE_RECOVERABLE", "0") == "1":
        jax.config.update("jax_enable_recoverability", True)
    jax.distributed.initialize(coordinator_address=f"{uri}:{port}",
                               num_processes=n, process_id=rank,
                               heartbeat_timeout_seconds=heartbeat)
    if jax.process_count() != n:
        raise MXNetError(
            f"distributed init came up with {jax.process_count()} "
            f"processes, expected {n}: the backend was initialized before "
            "init_distributed() — create the dist kvstore before touching "
            "any device")


def _coordination_client():
    """Handle to the coordination-service client, or None.

    JAX exposes no public liveness query, so this is the one sanctioned
    private touchpoint (everything else uses the public
    ``jax.distributed`` API). Guarded so a JAX upgrade that moves the
    internals degrades to a loud error rather than a silent wrong answer.
    Liveness itself has two spellings: newer jax clients expose
    ``get_live_nodes`` directly; older ones get ps-lite-style heartbeats
    over the coordination KV store (see KVStoreDistSync._start_heartbeats).
    """
    if not _dist_initialized():
        return None
    try:
        from jax._src import distributed as _dist
        client = getattr(_dist.global_state, "client", None)
    except ImportError:
        client = None
    if client is None:
        raise MXNetError(
            "jax.distributed is initialized but the coordination-service "
            "client is not reachable at jax._src.distributed.global_state."
            "client (JAX internals moved?); liveness queries unavailable")
    return client


def _ctype_key_value(key, vals):
    """Normalize to (list_of_keys, list_of_list_of_NDArray)."""
    if isinstance(key, (int, str)):
        key = [key]
        vals = [vals]
    out_vals = []
    for v in vals:
        if isinstance(v, NDArray):
            out_vals.append([v])
        else:
            out_vals.append(list(v))
    return list(key), out_vals


class KVStore:
    """Single-process store ('local'/'device'). reference:
    src/kvstore/kvstore_local.h:40-130."""

    def __init__(self, kind="local"):
        self._kind = kind
        self._store = {}
        self._updater = None
        self._optimizer = None
        self._closed = False

    # ---------------------------------------------------------------- meta
    @property
    def type(self):
        return self._kind

    @property
    def rank(self):
        return 0

    @property
    def num_workers(self):
        return 1

    # ---------------------------------------------------------------- core
    def init(self, key, value):
        keys, vals = _ctype_key_value(key, value)
        for k, vlist in zip(keys, vals):
            if k in self._store:
                raise MXNetError(f"key {k!r} already initialized")
            self._store[k] = vlist[0].copy()

    def push(self, key, value, priority=0):
        """Reduce values; run updater or assign (reference semantics:
        kvstore_local.h Push -> Comm::Reduce -> updater/assign)."""
        keys, vals = _ctype_key_value(key, value)
        if _telemetry.enabled():
            nbytes = _payload_bytes(vals)
            _telemetry.counter("kvstore.push.bytes").inc(nbytes)
            push_span = _telemetry.span(
                "kvstore.push", _hist="kvstore.push.seconds",
                keys=len(keys), bytes=nbytes)
        else:
            push_span = _telemetry.span("kvstore.push", keys=len(keys))
            _telemetry.flightrec.note("kvstore.push", keys=len(keys))
        try:
            with push_span:
                for k, vlist in zip(keys, vals):
                    if k not in self._store:
                        raise MXNetError(f"key {k!r} not initialized")
                    if len(vlist) == 1:
                        acc = vlist[0].asjax()
                    else:
                        acc = vlist[0].asjax()
                        for v in vlist[1:]:
                            acc = acc + v.asjax()
                    # colocate the merged value with the store replica:
                    # a mesh-replicated gradient pushed into a single-
                    # device store (multi-device Module + device store)
                    # would otherwise hand the updater incompatible
                    # placements
                    store_sharding = self._store[k].asjax().sharding
                    if acc.sharding != store_sharding:
                        acc = jax.device_put(acc, store_sharding)
                    elif len(vlist) == 1:
                        acc = jnp.array(acc, copy=True)
                    merged = NDArray(acc, ctx=vlist[0].context)
                    if self._updater is not None:
                        self._updater(k, merged, self._store[k])
                    else:
                        self._store[k]._set(merged.asjax())
        except Exception as exc:
            _telemetry.flightrec.on_crash(exc, where="kvstore.push")
            raise

    def pull(self, key, out=None, priority=0):
        """Broadcast stored values into out arrays.

        All destinations of the call are placed through ONE batched
        ``jax.device_put`` (a pytree of sources against a pytree of
        shardings) instead of one transfer per key: a 100-param pull
        was 100 separate transfers."""
        assert out is not None
        self._flush_pending()
        keys, outs = _ctype_key_value(key, out)
        if _telemetry.enabled():
            nbytes = _payload_bytes(outs)
            _telemetry.counter("kvstore.pull.bytes").inc(nbytes)
            pull_span = _telemetry.span(
                "kvstore.pull", _hist="kvstore.pull.seconds",
                keys=len(keys), bytes=nbytes)
        else:
            pull_span = _telemetry.span("kvstore.pull", keys=len(keys))
            _telemetry.flightrec.note("kvstore.pull", keys=len(keys))
        try:
            with pull_span:
                srcs, shardings, targets = [], [], []
                for k, olist in zip(keys, outs):
                    if k not in self._store:
                        raise MXNetError(f"key {k!r} not initialized")
                    src = self._store[k]
                    for o in olist:
                        # land the value in the destination's existing
                        # placement (keeps mesh-sharded arrays sharded)
                        srcs.append(src.asjax())
                        shardings.append(o.asjax().sharding)
                        targets.append(o)
                if srcs:
                    placed = jax.device_put(srcs, shardings)
                    for o, val in zip(targets, placed):
                        o._set(val)
        except Exception as exc:
            _telemetry.flightrec.on_crash(exc, where="kvstore.pull")
            raise

    def _flush_pending(self):
        """Apply deferred pushes (dist bucket scheduler); no-op here."""

    def close(self, abort=False):
        """Release background resources (dist heartbeats). Idempotent on
        every store kind; ``abort=True`` (dist) additionally drops any
        staged-but-undispatched gradients instead of flushing them —
        the right teardown when a peer is dead and a flush would fail
        against the broken collective."""
        self._closed = True

    # ------------------------------------------------------ failure surface
    def get_dead_nodes(self, timeout_ms=2000):
        """Ranks currently considered dead (single-process: none)."""
        return []

    def on_dead_node(self, callback, period=None):
        """Register a dead-worker callback. The dist store arms a
        watcher thread that fires ``callback(dead_ranks)`` once on the
        first detection; a single-process store has no peers to lose,
        so this is a documented no-op returning False."""
        return False

    # ------------------------------------------------------------ optimizer
    def set_optimizer(self, optimizer):
        """reference: kvstore.py:226 — local mode installs the updater
        closure; dist mode ships the (pickled) optimizer to the server.
        Here there is no server: always install locally."""
        self._optimizer = optimizer
        self._set_updater(opt.get_updater(optimizer))

    def _set_updater(self, updater):
        self._updater = updater

    def _barrier(self):
        pass

    def _send_command_to_servers(self, head, body):
        pass

    # --------------------------------------------------------- persistence
    def save_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("no updater/optimizer set")
        self._flush_pending()       # states must reflect every push
        states = {k: v.asnumpy() if isinstance(v, NDArray) else v
                  for k, v in getattr(self._updater, "states", {}).items()}
        with open(fname, "wb") as fout:
            pickle.dump(states, fout)

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("no updater/optimizer set")
        with open(fname, "rb") as fin:
            states = pickle.load(fin)
        self._updater.states.update(states)


class KVStoreDistSync(KVStore):
    """dist_sync over the JAX distributed runtime.

    reference semantics: kvstore_dist.h ZPush/ZPull + server merge-all-then-
    update (kvstore_dist_server.h:164-198). Realization: every process holds
    a replica; push() all-reduces the gradients across processes (one XLA
    collective over DCN/ICI per bucket), then the updater runs identically
    on every replica — the arithmetic invariant of dist_sync (nightly test
    formula) holds because sum-then-update on N replicas == server-side
    update.

    Unlike the reference's per-key ZPush, a multi-key push() batches every
    key of the call into large flat buckets (cap: MXNET_KVSTORE_BUCKET_BYTES,
    default 64 MiB) and all-reduces each bucket as ONE jitted XLA program —
    the analog of the reference batching gradients into its pinned merge
    buffers (comm.h InitMergeBuffer).

    Buckets run through a ready-order scheduler (kvstore_sched.py):
    ``push`` only *stages* gradients — in priority order, reverse
    execution order for Module's grads — and each bucket's collective
    dispatches asynchronously the moment the bucket fills, pipelining
    behind backward compute and each other. The host blocks (and the
    updater runs) only at ``pull``/barrier/state reads. Set
    ``MXNET_KVSTORE_OVERLAP=0`` to apply every push synchronously (the
    pre-overlap serial behavior).
    """

    _HB_PREFIX = "mxnet_kvstore_heartbeat/"

    def __init__(self, kind):
        super().__init__(kind)
        init_distributed()
        self._nproc = jax.process_count()
        self._mesh = None
        self._sum_jit = None
        self._sum_jit_shapes = set()     # (dtype, padded-len) size classes
        self._hb_stop = None
        self._hb_thread = None
        self._watch_stop = None          # dead-node watcher (on_dead_node)
        self._watch_thread = None
        self._closed = False
        # fleet identity: ring records / trace spans / ops endpoint now
        # resolve their rank from this live store (weakref'd — a closed
        # store stops answering)
        _telemetry.fleet.register_kvstore(self)
        self._sched = BucketScheduler(
            self._allreduce_flat, self._apply_reduced,
            lambda: int(os.environ.get("MXNET_KVSTORE_BUCKET_BYTES",
                                       64 << 20)))
        if self._nproc > 1:
            client = _coordination_client()
            if client is not None and not hasattr(client,
                                                  "get_live_nodes"):
                self._start_heartbeats(client)

    def _start_heartbeats(self, client):
        """ps-lite-style heartbeats for jax builds whose coordination
        client has no ``get_live_nodes``: each rank periodically writes
        its wall clock under a well-known key in the coordination KV
        store (reference: ps-lite van.cc Heartbeat), and
        ``get_num_dead_node`` counts ranks whose last beat went stale.
        The first beat lands synchronously so a freshly constructed
        store is immediately visible to its peers."""
        import threading
        import time as _time
        horizon = int(os.environ.get("PS_HEARTBEAT_TIMEOUT", "100"))
        period = max(1.0, horizon / 3.0)
        key = f"{self._HB_PREFIX}{self.rank}"

        def beat():
            try:
                client.key_value_set(key, repr(_time.time()),
                                     allow_overwrite=True)
            except Exception:
                pass        # a dying coordinator must not kill training

        beat()
        stop = threading.Event()

        def loop():
            while not stop.wait(period):
                beat()

        thread = threading.Thread(target=loop, daemon=True,
                                  name="mxnet-kvstore-heartbeat")
        thread.start()
        self._hb_stop = stop
        self._hb_thread = thread

    def close(self, abort=False):
        """Flush pending pushes and stop/join the heartbeat and
        dead-node watcher threads so a discarded store can't leak
        threads across a test suite (or keep beating for a rank that
        logically left the job). Idempotent: a second close is a no-op,
        so teardown paths (fit cleanup, recovery, __del__, user code)
        can all call it without coordination. ``abort=True`` drops any
        staged-but-undispatched gradients instead of flushing — the
        recovery teardown, where a flush would re-enter the collective
        a dead peer already broke."""
        if self._closed:
            return
        self._closed = True
        if abort:
            self._sched.drop_pending()
        else:
            self._flush_pending()
        for stop, thread in ((self._watch_stop, self._watch_thread),
                             (self._hb_stop, self._hb_thread)):
            if stop is not None:
                stop.set()
                if thread is not None and \
                        thread is not threading.current_thread():
                    thread.join(timeout=5)
        self._watch_stop = self._watch_thread = None
        self._hb_stop = self._hb_thread = None

    def __del__(self):
        try:
            for stop in (self._hb_stop, self._watch_stop):
                if stop is not None:
                    stop.set()
        except Exception:
            pass        # interpreter teardown

    @property
    def rank(self):
        return jax.process_index()

    @property
    def num_workers(self):
        return self._nproc

    # ------------------------------------------------------- collective core
    def _ensure_mesh(self):
        if self._mesh is not None:
            return
        from jax.sharding import Mesh, PartitionSpec, NamedSharding
        # (process x local-device) mesh: every chip on every host joins
        # the reduction — the analog of the reference's dist_device_sync
        # (local GPU reduce + PS across nodes, comm.h:289-361). The
        # buffer is split over the local axis, so each local device
        # reduces (and moves over DCN) only its slice, multiplying
        # cross-host bandwidth by the local device count.
        by_proc = {}
        for d in sorted(jax.devices(), key=lambda d: (d.process_index,
                                                      d.id)):
            by_proc.setdefault(d.process_index, []).append(d)
        counts = {len(v) for v in by_proc.values()}
        if len(counts) != 1:
            raise MXNetError(
                f"uneven local device counts across processes: "
                f"{sorted(counts)}")
        self._local = counts.pop()
        devs = np.array([by_proc[p] for p in range(self._nproc)])
        self._mesh = Mesh(devs, ("proc", "dev"))
        self._pspec = PartitionSpec
        self._sum_jit = jax.jit(
            lambda x: jnp.sum(x, axis=0),
            out_shardings=NamedSharding(self._mesh,
                                        PartitionSpec("dev")))

    def _size_class(self, n):
        """Padded length for a flat buffer: the local device count L
        times the next power of two of ceil(n/L). Tiny/odd gradient
        lengths then share O(log max-size) padded shapes instead of
        minting a fresh ``_sum_jit`` trace per unique length."""
        chunk = max(1, -(-n // self._local))
        chunk = 1 << (chunk - 1).bit_length()
        return chunk * self._local

    def _allreduce_flat(self, flat):
        """All-reduce one 1-D buffer, retrying transient failures.

        The dispatch is wrapped in the shared retry policy
        (``MXNET_RETRY_COLLECTIVE``, docs/faults.md): a TRANSIENT
        collective error (flaky DCN link, coordination-service blip, an
        injected ``kvstore.collective`` fault) retries with backoff and
        is invisible to the caller; a failure with an actually-dead
        peer converts to :class:`checkpoint.DeadWorkerError` IMMEDIATELY
        (the liveness layer decides — burning the backoff budget
        against a peer that will never answer just delays recovery); a
        persistent failure with every peer alive re-raises the original
        error after the policy gives up (a real bug, not a death).
        Retry is safe here because a failed dispatch applied nothing:
        every worker that failed re-enters the same collective in the
        same order (policies must match across workers — env-configured,
        docs/faults.md). Failures surfacing later, at the flush-side
        ``block_until_ready``, go through ``Module.fit``'s existing
        dead-worker conversion instead.
        """
        def give_up(exc):
            from .checkpoint.recovery import DeadWorkerError
            if isinstance(exc, DeadWorkerError):
                return exc
            try:
                dead = self.get_dead_nodes()
            except Exception:
                dead = []
            if dead:
                _telemetry.flightrec.note("recovery.dead_worker",
                                          ranks=list(dead), clean=False,
                                          where="kvstore.collective")
                return DeadWorkerError(dead, clean=False)
            return None

        return _faults.retry_call(
            lambda: self._allreduce_flat_once(flat),
            _faults.RetryPolicy.from_env("COLLECTIVE", attempts=3,
                                         base_s=0.02, max_s=0.5),
            site="kvstore.collective", give_up=give_up,
            logger=logging.getLogger(__name__))

    def _allreduce_flat_once(self, flat):
        """One all-reduce attempt across all devices of all processes.

        Layout: pad to the power-of-two size class (multiple of the
        local device count L), view as (1, L, chunk) sharded
        (proc, dev), sum over proc with the result sharded over dev;
        every process then reassembles the full reduced buffer from its
        own local shards (replicated-across-proc output). Single-process
        stores run the same program over the (1, L) mesh — the
        local-device reduction path is identical, only the proc axis is
        trivial.
        """
        _faults.point("kvstore.collective")
        from jax.experimental import multihost_utils
        self._ensure_mesh()
        if _telemetry.enabled():
            nbytes = int(flat.size) * flat.dtype.itemsize
            ar_span = _telemetry.span(
                "kvstore.allreduce", _hist="kvstore.allreduce.seconds",
                bytes=nbytes)
        else:
            ar_span = _telemetry.span("kvstore.allreduce")
        with ar_span:
            n = flat.shape[0]
            padded = self._size_class(n)
            if padded != n:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((padded - n,), flat.dtype)])
            self._sum_jit_shapes.add((str(flat.dtype), padded))
            _telemetry.gauge("kvstore.allreduce.size_classes").set(
                len(self._sum_jit_shapes))
            from jax.sharding import NamedSharding
            if self._nproc == 1:
                # single process owns every mesh device: plain resharding
                # device_puts replace the multihost host-local<->global
                # conversions, keeping the whole reduction async (no host
                # sync at dispatch — the overlap window of the bucket
                # scheduler)
                x = flat.reshape(1, self._local, -1)
                glob = jax.device_put(
                    x, NamedSharding(self._mesh,
                                     self._pspec("proc", "dev")))
                return jnp.ravel(self._sum_jit(glob))[:n]
            # a gradient pushed from a multi-device (mesh-replicated)
            # executor arrives with >1 local shard; the host-local
            # conversion below needs ONE process-local array
            if getattr(flat, "sharding", None) is not None and \
                    len(flat.sharding.device_set) > 1:
                flat = jax.device_put(
                    flat, flat.addressable_shards[0].device)
            x = flat.reshape(1, self._local, -1)
            glob = multihost_utils.host_local_array_to_global_array(
                x, self._mesh, self._pspec("proc", "dev"))
            red = self._sum_jit(glob)
            loc = multihost_utils.global_array_to_host_local_array(
                red, self._mesh, self._pspec("dev"))
            out = jnp.ravel(loc)
            return out[:n] if padded != n else out

    def _allreduce(self, arrs):
        """Unbucketed reference path: one collective per array. The hot
        path is the bucket scheduler (push/_sched); this remains as the
        equivalence oracle the bucketed path is tested against."""
        return [self._allreduce_flat(jnp.ravel(jnp.asarray(a.asjax()
                if isinstance(a, NDArray) else a))).reshape(a.shape)
                for a in arrs]

    # ----------------------------------------------------------------- push
    def push(self, key, value, priority=0):
        """Stage gradients into the ready-order bucket scheduler.

        ``priority`` may be a scalar (the reference API) or one value
        per key; higher priorities dispatch earlier. Collectives for
        full buckets go on the wire inside this call — asynchronously —
        and the updater runs at the next ``pull``/barrier/state read
        (immediately under ``MXNET_KVSTORE_OVERLAP=0``)."""
        keys, vals = _ctype_key_value(key, value)
        prios = list(priority) if isinstance(priority, (list, tuple)) \
            else [priority] * len(keys)
        if len(prios) != len(keys):
            raise MXNetError(
                f"got {len(prios)} priorities for {len(keys)} keys")
        if _telemetry.enabled():
            nbytes = _payload_bytes(vals)
            _telemetry.counter("kvstore.push.bytes").inc(nbytes)
            push_span = _telemetry.span(
                "kvstore.push", _hist="kvstore.push.seconds",
                keys=len(keys), bytes=nbytes, dist=True)
        else:
            push_span = _telemetry.span("kvstore.push", keys=len(keys),
                                        dist=True)
            _telemetry.flightrec.note("kvstore.push", keys=len(keys),
                                      dist=True)
        try:
            with push_span:
                # one arrival epoch per caller-level push: the static
                # collective-order checker (analysis rule CO301) treats
                # equal-priority keys from different epochs as
                # ready-order — i.e. nondeterministic across workers
                self._sched.note_push_call()
                for k, vlist, prio in zip(keys, vals, prios):
                    if k not in self._store:
                        raise MXNetError(f"key {k!r} not initialized")
                    acc = vlist[0].asjax()
                    for v in vlist[1:]:
                        acc = acc + v.asjax()
                    self._sched.stage(k, vlist[0].context, acc, prio)
                if os.environ.get("MXNET_KVSTORE_OVERLAP", "1") == "0":
                    self._sched.flush()
        except Exception as exc:
            _telemetry.flightrec.on_crash(exc, where="kvstore.push")
            raise

    def _apply_reduced(self, k, ctx, red):
        """Scheduler callback: one key's bucket segment, reduced."""
        # The bucketed all-reduce hands back each value sharded over the
        # local `dev` mesh axis (bandwidth layout). The store replica and
        # its optimizer state live wherever the user placed the weight —
        # re-place the reduced gradient there so the updater's inputs are
        # colocated (the analog of the reference copying the merged
        # buffer back to each GPU, comm.h Broadcast).
        store_sharding = self._store[k].asjax().sharding
        if red.sharding != store_sharding:
            red = jax.device_put(red, store_sharding)
        nd_val = NDArray(red, ctx=ctx)
        if self._updater is not None:
            self._updater(k, nd_val, self._store[k])
        else:
            self._store[k]._set(nd_val.asjax())

    def _flush_pending(self):
        try:
            self._sched.flush()
        except Exception as exc:
            _telemetry.flightrec.on_crash(exc, where="kvstore.push")
            raise

    def _barrier(self):
        self._flush_pending()
        if self._nproc > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("kvstore_barrier")

    # ------------------------------------------------------ failure surface
    def get_dead_nodes(self, timeout_ms=2000):
        """Ranks currently considered dead (reference:
        kvstore_dist.h:159-168 GetDeadNodes over ps-lite heartbeats).
        One-sided: queries the coordination service's liveness tracking
        (``get_live_nodes`` where the client has it, else this store's
        own heartbeat keys) — any single rank can call this at any
        time, no peer cooperation needed. ``timeout_ms`` bounds the
        per-rank key wait in the heartbeat fallback; the native path
        applies the service's own heartbeat timeout. Returns a sorted
        rank list, the input the elastic-recovery rank remapping
        (checkpoint/recovery.survivor_env) needs — a bare count can't
        say WHO to exclude from the re-formed job."""
        if self._nproc <= 1:
            return []
        client = _coordination_client()
        if client is None:
            return []
        me = self.rank
        if hasattr(client, "get_live_nodes"):
            live = set(client.get_live_nodes(list(range(self._nproc))))
            return sorted(r for r in range(self._nproc)
                          if r not in live and r != me)
        # heartbeat fallback: a rank whose beat is missing or older than
        # PS_HEARTBEAT_TIMEOUT counts as dead (its last value stays in
        # the KV store, so a crashed peer reads back instantly as stale)
        import time as _time
        horizon = float(os.environ.get("PS_HEARTBEAT_TIMEOUT", "100"))
        wait_ms = max(100, int(timeout_ms) // self._nproc)
        dead = []
        for r in range(self._nproc):
            if r == me:
                continue    # a running rank can never observe itself dead
            try:
                ts = float(client.blocking_key_value_get(
                    f"{self._HB_PREFIX}{r}", wait_ms))
                if _time.time() - ts > horizon:
                    dead.append(r)
            except Exception:
                dead.append(r)      # never wrote a beat: not alive yet
        return dead

    def get_num_dead_node(self, node_id=0, timeout_ms=2000):
        """Count of dead workers (the reference-shaped polling API;
        ``get_dead_nodes`` adds the rank identities)."""
        return len(self.get_dead_nodes(timeout_ms=timeout_ms))

    def on_dead_node(self, callback, period=None):
        """Arm a watcher thread that calls ``callback(dead_ranks)`` ONCE
        when the liveness layer first reports a dead peer — the push
        seam the elastic-recovery path hangs off (polling
        ``get_num_dead_node`` from the training loop would either lag
        detection by a batch or tax every batch with a liveness RPC).

        The callback runs on the watcher thread: implementations should
        only record the event (set a flag, bump a counter) and let the
        training thread act at its next safe boundary. The watcher
        exits after firing (re-arm by calling again); ``close()`` stops
        an unfired watcher. Returns True when armed, False when there
        is nothing to watch (single process)."""
        if self._nproc <= 1 or self._closed:
            return False
        if self._watch_stop is not None:
            self._watch_stop.set()          # replace a previous watcher
        horizon = float(os.environ.get("PS_HEARTBEAT_TIMEOUT", "100"))
        period = max(0.2, horizon / 5.0) if period is None else \
            float(period)
        stop = threading.Event()

        def watch():
            while not stop.wait(period):
                try:
                    dead = self.get_dead_nodes()
                except Exception:
                    continue        # a flaky liveness query isn't a death
                if dead:
                    _telemetry.counter("recovery.events").inc()
                    _telemetry.flightrec.note("recovery.dead_node",
                                              ranks=list(dead))
                    if _telemetry.enabled():
                        _telemetry.record_event("dead_node",
                                                ranks=list(dead))
                    try:
                        callback(list(dead))
                    except Exception:
                        logging.getLogger(__name__).exception(
                            "on_dead_node callback failed")
                    return
        thread = threading.Thread(target=watch, daemon=True,
                                  name="mxnet-kvstore-deadwatch")
        thread.start()
        self._watch_stop = stop
        self._watch_thread = thread
        return True


def create(name="local"):
    """Factory. reference: src/kvstore/kvstore.cc:17-45 (substring match)."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    if "dist_async" in name:
        raise MXNetError(
            "dist_async has no TPU-native equivalent: asynchronous "
            "parameter-server updates do not map onto XLA collectives "
            "(SURVEY.md §7). Use dist_sync (all-reduce) instead.")
    if "dist" in name:
        return KVStoreDistSync(name)
    if "device" in name or "local" in name:
        return KVStore(name)
    raise MXNetError(f"unknown kvstore type {name!r}")
