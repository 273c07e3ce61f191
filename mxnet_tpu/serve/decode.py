"""Continuous decode batching: iteration-level scheduling over a
slot-pooled KV cache (the Orca-style serving path, ROADMAP 3b).

``InferenceServer`` batches one-shot requests; a KV-cache decoder is a
*sequence* — hundreds of dispatches carrying device state between
them, a token each or, of a model that decodes by blocks, a block's
positions fed until all are decided — and serving it one sequence at
a time pins decode
throughput at batch 1. This module serves SLOTS sequences through ONE
pinned program per iteration:

* ``DecodeEngine`` — a slot-capacity rung ladder (``MXNET_SERVE_DECODE_
  SLOTS``, default ``1,4,8``) over ``get_decode_symbol(per_slot=True)``
  graphs: every rung is a Module bound at ``(slots, 1)`` sharing ONE
  set of parameter cells (``BucketingModule``/shared_module, exactly
  like the batch bucket ladder) with its own slot-pooled
  ``(slots, H, C, Dh)`` KV-cache aux; ``warmup`` compiles and PINS
  every rung, after which join/leave/rung-switches never mint a trace —
  ``compiles_since_warmup()`` stays 0. Rung switches migrate the live
  slots' cache rows + cursors between rung pools with eager per-row
  copies (no program-cache entries).
* ``DecodeScheduler`` — iteration-level continuous batching on the
  ``submit`` seam: prefill admission into free slots, per-iteration
  retirement (EOS / max-new-tokens / deadline / per-slot cache
  overflow — an overflowing slot fails ALONE, batchmates keep
  decoding), temperature/top-k/top-p sampling on a recorded
  per-request rng chain (``SamplingParams``; default greedy), and
  streaming token delivery through ``DecodeHandle`` callbacks. Token
  ids cross to the host, not logits: a select program behind every
  step picks each slot's last fed row and takes its argmax on the
  device; the rows themselves are fetched only for an iteration in
  which a slot that samples is not greedy. One dispatch may be in
  flight ahead of the host: where the scheduler can plan the next
  dispatch, window or S=1 step, without the ids of the one on the chip
  (whoever samples there and stays is greedy; whoever waits is
  admitted into a free slot of the same rung), it is launched before
  those ids are fetched, the slots
  that sampled fed from the chip by the select program and the slots
  that still prefill by the host, and committed an iteration later
  (``DecodeScheduler._plan_ahead``); every other dispatch is planned
  after its predecessor's commit. Two
  drive modes, same as the server: ``start()`` (dispatch thread, real
  clock) and ``pump()`` (explicit iterations, FakeClock-deterministic).

Three decode fast paths ride the same rungs (all preserve the
zero-steady-state-compile contract — every program they need is
compiled and pinned at warmup):

* **Chunked prefill** — each rung carries an S-token *window* program
  (``MXNET_SERVE_PREFILL_CHUNK``, default 64) next to its S=1 decode
  program, so a T-token prompt prefills in ⌈T/S⌉ dispatches instead of
  T and TTFT goes near-flat in prompt length. Slots mid-decode ride a
  chunk dispatch with one real token plus pads. The graph is fed its
  real tokens a slot (``fed``: every graph ``get_decode_symbol(per_slot=
  True)`` builds, and a graph without the input is refused) and
  advances each slot by those alone, so mixed prefill/decode iterations
  lose nothing and nobody rewinds after a window. It also gets, where
  that at least halves the rows, the *packed* form of its window
  program, whose row-wise operations run over a budget R and not ``slots x S`` (``models.transformer
  .packed_rows``: a chunk and a token a slot, and never under the rows
  a weight-bound matmul carries for free); the scheduler then plans
  every window inside R - a token for each decoding slot, the rest to
  the prefilling slots oldest first (``DecodeScheduler._plan_window``)
  - so the chunk is prefill tokens a dispatch, not a slot.
* **Prefix-cache reuse** — ``submit(prefix_id=...)`` names a shared
  prompt prefix; the first completion snapshots its cache rows into a
  ``PrefixStore`` (LRU under ``MXNET_SERVE_PREFIX_CACHE_MB``, charged
  by the static memory planner) and later submits *join at cursor C*
  with the rows written back — bitwise what a cold prefill computes.
* **Speculative decoding** — a draft engine proposes
  ``MXNET_SERVE_SPEC_K`` tokens per iteration (K cheap S=1 dispatches)
  and the target verifies them in ONE S=K window dispatch over the
  per-slot cursor vector (slots verify at staggered positions); exact
  rejection sampling keeps the output distributionally identical to
  target-only decode and bit-identical under greedy, with rejected
  tails rolled back by cursor rewind on both engines.

**A model that decodes by blocks** (``models.transformer
.decode_procedure(symbol)``: the graph says so, nothing is passed in):
the engine adds the program of one block's ``L`` rows a slot on every
rung beside the prefill chunk, and the scheduler plans a decoding slot
as its block - ``L`` ids at a cursor that is a multiple of ``L``, the
undecided positions the mask id -, launches ``denoise_select`` behind
the step (ids and a mask come back, never logits), takes a feed that
still had undecided positions back (``rewind_many``: its keys and
values are not kept), streams every token no earlier position of which
is undecided, and advances the cursor by a clean feed alone. A window
carries prefill chunks in multiples of ``L`` and the decoding slots
wait that iteration. A block dispatch is launched behind the one on
the chip like any other (``_plan_block_ahead``): the ids, the mask and
whether the feed keeps its rows are taken from ``denoise_select``'s
result as it lies there (docs/serving.md, "A model that decodes by
blocks").

Per-sequence traces survive being batched with strangers: every
sequence keeps its own session trace (root span
``serve.decode.sequence``), and each iteration records ONE shared
``serve.decode.step`` span id mirrored into every active sequence's
trace — the same shared-dispatch-span contract batched requests follow.

Telemetry (always on, docs/serving.md has the catalog):
``serve.decode.slots``/``active``/``occupancy``/``queue.depth`` gauges,
``serve.decode.iterations``/``tokens``/``joins``/``leaves``/
``migrations``/``requests``/``responses``/``errors``/``fetch.bytes``/
``sample.device``/``sample.host``/``state.donated_bytes``/
``runahead.launched``/``runahead.windows``/``runahead.blocks``/
``runahead.dropped``/``window.dispatches`` counters, of a block engine also
``diffusion.feeds``/``blocks``/``decided``/``rows_dropped``/
``undelivered``,
``serve.decode.step.seconds`` + ``serve.decode.request.latency.seconds``
histograms, and one flight-ring record per iteration.
"""
from __future__ import annotations

import collections
import itertools
import logging
import os
import threading

import numpy as np
import jax.numpy as jnp

from .. import program_cache as _progcache
from .. import telemetry as _telemetry
from ..telemetry import trace as _trace
from ..base import MXNetError
from ..io import DataDesc
from .batching import BucketLadder, QueueFullError
from .clock import MonotonicClock
from .prefix import PrefixStore
from .sampling import SamplingParams, sample_token, token_probs, \
    speculative_verify

__all__ = ["DecodeEngine", "DecodeScheduler", "DecodeHandle",
           "default_slot_ladder", "default_prefill_chunk",
           "default_spec_k", "serve_decoder"]

log = logging.getLogger(__name__)

_seq_ids = itertools.count()

_GREEDY = SamplingParams()

# what a forward does besides its program (``Module.forward``'s own
# counters, process-wide, counted while ``telemetry.enabled()``)
_LAUNCH_COUNTERS = ("io.load_batch.puts", "io.load_batch.aliased",
                    "executor.rng.draws")


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _us(seconds):
    """Whole microseconds of a clock difference, never negative."""
    return int(max(0.0, seconds) * 1e6)


def default_prefill_chunk():
    """``MXNET_SERVE_PREFILL_CHUNK`` (docs/env_var.md), default 64:
    prompt tokens per prefill dispatch. 1 disables chunking (token-at-
    a-time prefill, the pre-window behavior)."""
    return max(1, _env_int("MXNET_SERVE_PREFILL_CHUNK", 64))


def default_spec_k():
    """``MXNET_SERVE_SPEC_K`` (docs/env_var.md), default 4: draft
    tokens proposed (and verified in one window dispatch) per
    speculative iteration."""
    return max(2, _env_int("MXNET_SERVE_SPEC_K", 4))


def default_slot_ladder():
    """The slot-capacity rung ladder from ``MXNET_SERVE_DECODE_SLOTS``
    (default ``1,4,8``): comma-separated concurrent-sequence capacities,
    sorted ascending, duplicates dropped — the decode-side analog of
    ``MXNET_SERVE_BUCKETS``."""
    raw = os.environ.get("MXNET_SERVE_DECODE_SLOTS", "1,4,8")
    try:
        sizes = sorted({int(tok) for tok in raw.split(",") if tok.strip()})
    except ValueError:
        raise MXNetError(f"MXNET_SERVE_DECODE_SLOTS={raw!r}: expected "
                         "comma-separated slot counts")
    if not sizes or sizes[0] < 1:
        raise MXNetError(f"MXNET_SERVE_DECODE_SLOTS={raw!r}: slot "
                         "counts must be >= 1")
    return sizes


class _Sequence:
    """One admitted decode request's scheduling state.

    The *stream* is ``prompt ++ generated``; ``fed`` counts stream
    tokens whose cache rows are written (= the slot's device cursor).
    An iteration feeds ``stream[fed : fed + n]`` in one dispatch and
    advances ``fed`` by the tokens it actually committed — in steady
    state ``fed == stream_len() - 1`` (the last sampled token is fed
    next), during prefill ``stream_len() - fed > 1``.
    """

    __slots__ = ("id", "prompt", "max_new", "eos_id", "arrival",
                 "deadline", "trace", "root_sid", "handle", "fed",
                 "generated", "slot", "finish_reason", "sampling",
                 "rng", "first_dispatch_at", "prefix_id", "prefix_cold",
                 "block_len", "block", "stopped")

    def __init__(self, prompt, max_new, eos_id, arrival, deadline,
                 trace=None, sampling=None, prefix_id=None, block_len=0):
        self.id = next(_seq_ids)
        self.prompt = prompt
        self.max_new = max_new
        self.eos_id = eos_id
        self.arrival = arrival
        self.deadline = deadline          # absolute clock s, or None
        self.trace = trace
        self.root_sid = None
        self.fed = 0                      # stream tokens fed = cursor
        self.generated = []
        self.slot = None
        self.finish_reason = None
        self.sampling = sampling if sampling is not None else _GREEDY
        self.rng = self.sampling.make_rng()
        self.first_dispatch_at = None     # first dispatch covering us
        self.prefix_id = prefix_id
        self.prefix_cold = False          # missed: capture after prefill
        # of an engine that decodes by blocks (``block_len``: a block's
        # positions; 0 of any other): the block in flight, and why the
        # stream stopped inside it ("eos") until its commit retires the
        # request
        self.block_len = block_len
        self.block = None
        self.stopped = None
        self.handle = DecodeHandle(self)

    def stream_len(self):
        return len(self.prompt) + len(self.generated)

    def stream_token(self, i):
        if i < len(self.prompt):
            return int(self.prompt[i])
        return int(self.generated[i - len(self.prompt)])

    def remaining(self):
        """Stream tokens not yet fed (1 in steady state; > 1 while
        prefilling)."""
        return self.stream_len() - self.fed

    def window(self, at, n):
        """The ``n`` stream tokens from position ``at`` on."""
        return [self.stream_token(at + j) for j in range(n)]

    def prefill_left(self):
        """Of a sequence decoded by blocks: the prompt tokens still to
        prefill - the prompt's whole blocks less the cursor; its last
        ``P mod L`` tokens are the first block's."""
        whole = len(self.prompt) // self.block_len * self.block_len
        return max(0, whole - self.fed)


class _Block:
    """A block in flight: its ``L`` ids as the next feed takes them
    (the mask id at an undecided position), which positions are
    undecided - BY POSITION: a prompt may hold the mask id -, and the
    feeds it has had. It starts at the cursor ``at``: the sequence's
    own, or where the commit of a dispatch still on the chip will
    leave it."""

    __slots__ = ("ids", "undecided", "feeds")

    def __init__(self, seq, mask_id, at=None):
        L, at = seq.block_len, seq.fed if at is None else at
        held = max(0, min(L, len(seq.prompt) - at))   # the prompt's tail
        self.ids = np.full(L, mask_id, np.int32)
        self.ids[:held] = seq.prompt[at:at + held]
        self.undecided = np.arange(L) >= held
        self.feeds = 0


class DecodeHandle:
    """Streaming sync+async result surface for one decode request.

    Mirrors ``ResponseHandle`` (``done()``/``result()``/
    ``add_done_callback``/``latency``) and adds the streaming half:
    ``add_token_callback(fn)`` runs ``fn(handle, token, index)`` for
    every generated token — already-emitted tokens replay immediately
    on registration, so a late subscriber misses nothing. ``result()``
    returns the generated ids as an int32 numpy array (EOS excluded);
    ``finish_reason`` is ``"eos"``, ``"length"`` (max-new-tokens),
    ``"deadline"`` (partial result, deadline passed mid-decode), or
    None when the sequence errored (``exception()`` carries it).
    """

    def __init__(self, request):
        self._event = threading.Event()
        self._lock = threading.Lock()
        self._done_callbacks = []
        self._token_callbacks = []
        self._tokens = []
        self._error = None
        self.request = request
        self.completed_at = None        # scheduler-clock seconds
        self.first_token_at = None

    def done(self):
        return self._event.is_set()

    @property
    def trace_id(self):
        tr = self.request.trace
        return tr.trace_id if tr is not None else None

    @property
    def tokens(self):
        """Generated token ids so far (list copy — streaming-safe)."""
        with self._lock:
            return list(self._tokens)

    @property
    def finish_reason(self):
        return self.request.finish_reason

    @property
    def latency(self):
        """Admission-to-completion seconds (None until done)."""
        if self.completed_at is None:
            return None
        return self.completed_at - self.request.arrival

    @property
    def ttft(self):
        """Submit-to-first-token seconds, queue wait INCLUDED (None
        before the first token)."""
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.request.arrival

    @property
    def ttft_exec(self):
        """First-dispatch-to-first-token seconds: the prefill cost the
        engine actually paid, with queue wait excluded — the number the
        chunked-prefill win shows up in under load."""
        if self.first_token_at is None or \
                self.request.first_dispatch_at is None:
            return None
        return self.first_token_at - self.request.first_dispatch_at

    def missed_deadline(self):
        return (self.completed_at is not None
                and self.request.deadline is not None
                and self.completed_at > self.request.deadline)

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise MXNetError(
                f"decode request {self.request.id} not complete within "
                f"{timeout}s (scheduler stopped or stuck?)")
        if self._error is not None:
            raise self._error
        return np.asarray(self._tokens, np.int32)

    def exception(self):
        return self._error if self._event.is_set() else None

    def add_done_callback(self, fn):
        with self._lock:
            if not self._event.is_set():
                self._done_callbacks.append(fn)
                return
        fn(self)

    def add_token_callback(self, fn):
        """Stream generated tokens: ``fn(handle, token, index)`` per
        token, starting with an immediate replay of any already
        emitted."""
        with self._lock:
            replay = list(enumerate(self._tokens))
            self._token_callbacks.append(fn)
        for i, tok in replay:
            self._safe(fn, tok, i)

    def _safe(self, fn, *args):
        try:
            fn(self, *args)
        except Exception:       # a client callback must not kill the
            pass                # scheduler thread

    def _emit(self, token, now=None):
        with self._lock:
            index = len(self._tokens)
            self._tokens.append(int(token))
            cbs = list(self._token_callbacks)
        if index == 0:
            self.first_token_at = now
        for fn in cbs:
            self._safe(fn, int(token), index)

    def _complete(self, error=None, now=None):
        with self._lock:
            self._error = error
            self.completed_at = now
            callbacks, self._done_callbacks = self._done_callbacks, []
            self._event.set()
        for fn in callbacks:
            self._safe(fn)


#: aux cells whose leading axis is the slot: what a rung switch carries


class DecodeEngine:
    """Slot-capacity rung ladder over a slot-pooled decode graph.

    ``symbol`` must be a per-slot stateful decode graph (for the LM
    workload: ``models.transformer.get_decode_symbol(per_slot=True)``)
    whose batch dim is the slot count — the SAME symbol binds at every
    rung, so all rungs share one parameter-cell set through the bucket
    leader while each owns its rung-sized KV-cache pool. ``capacity``
    defaults to the bound cache's (inferred from the aux shapes);
    ``pos_embed`` is detected from the graph (a ``pos_ids`` argument =
    learned positions, fed per slot by the drivers).

    ``symbol_gen`` (``step_len -> symbol``, e.g.
    ``lambda s: get_decode_symbol(per_slot=True, step_len=s)``) arms
    the S>1 *window* programs: for every ``window_lens`` entry W > 1,
    each rung gets a Module over ``symbol_gen(W)`` bound with
    ``shared_module=`` that rung's S=1 module — parameter cells chain
    to the bucket leader's and the KV-cache/cursor aux CELLS are shared
    outright (their shapes are step-independent), so the window program
    and the decode program advance the same device state. Window
    lengths clamp to ``capacity``; all window programs warm and pin
    alongside the rungs' S=1 programs.
    """

    def __init__(self, name, symbol, arg_params, aux_params=None,
                 capacity=None, ladder=None, context=None,
                 compute_dtype=None, logger=None, symbol_gen=None,
                 window_lens=()):
        from ..context import current_context
        from ..module import BucketingModule

        self.name = name
        self.ladder = ladder if isinstance(ladder, BucketLadder) \
            else BucketLadder(ladder if ladder is not None
                              else default_slot_ladder())
        self.exec_est = {}              # rung -> EMA'd step seconds
        self._warm_mark = None
        self._warm_backend_mark = None
        self._warm_launch_mark = None
        self.warmup_compiles = None
        self._symbol = symbol
        self._context = context if context is not None \
            else current_context()
        self.pos_embed = "learned" \
            if "pos_ids" in symbol.list_arguments() else "rotary"
        from ..models.transformer import decode_procedure
        # how the graph decodes where it says so: by blocks
        self.block = decode_procedure(symbol)
        self.block_len = None if self.block is None \
            else int(self.block["block_length"])
        # the state advances by the real tokens alone: their count a
        # slot goes in beside the tokens
        self.data_names = ("data",) + (
            ("pos_ids",) if self.pos_embed == "learned" else ()) + ("fed",)
        if not any(getattr(n.opdef(), "stateful_infer", False)
                   for n in symbol._topo_nodes() if not n.is_variable):
            raise MXNetError(
                f"DecodeEngine({name!r}): the symbol has no stateful "
                "decode op (build it with get_decode_symbol("
                "per_slot=True))")
        if "fed" not in symbol.list_arguments():
            raise MXNetError(
                f"DecodeEngine({name!r}): the symbol takes no fed, the "
                "real tokens of each slot (build it with "
                "get_decode_symbol(per_slot=True))")

        self._bm = BucketingModule(
            sym_gen=lambda slots: (symbol, list(self.data_names), []),
            default_bucket_key=self.ladder.max,
            logger=logger or log, context=self._context)
        if compute_dtype is not None:
            self._bm._module_kwargs["compute_dtype"] = compute_dtype
            # a serving binding keeps no masters: every parameter the
            # step programs would cast binds at the compute width,
            # whatever it is handed at, and is cast once as it is stored
            from ..module.executor_group import serving_width_params
            self._bm._module_kwargs["param_dtypes"] = \
                serving_width_params(symbol, self.data_names,
                                     compute_dtype)
        self._bm.bind(self._provide_data(self.ladder.max),
                      label_shapes=None, for_training=False)
        # straight to the leader with initializer=None: the decode
        # graph's aux states (KV cache + cursor) are absent from any
        # trained param set and must stay their bound zeros —
        # BucketingModule.init_params would fall back to Uniform and
        # trip over the cursor's name pattern
        self._bm._leader.init_params(initializer=None,
                                     arg_params=dict(arg_params or {}),
                                     aux_params=dict(aux_params or {}),
                                     allow_missing=True)
        self._bm.params_initialized = True
        self._bm._params_dirty = self._bm._leader._params_dirty
        self._note_params(arg_params)
        self._bm.warm_buckets(
            [(s, self._provide_data(s), None) for s in self.ladder])

        if capacity is None:
            # what the graph's decode ops were built with
            capacity = next(int(n.attrs["capacity"])
                            for n in symbol._topo_nodes()
                            if not n.is_variable and n.opdef().slot_state)
        self.capacity = int(capacity)

        from ..models.transformer import BatchedKVCacheDecoder
        self._drivers = {
            s: BatchedKVCacheDecoder(self._bm._buckets[s],
                                     self.capacity, slots=s,
                                     pos_embed=self.pos_embed, name=name)
            for s in self.ladder}

        self.window_lens = sorted(
            {min(int(w), self.capacity) for w in (window_lens or ())}
            - {0, 1})
        if self.block is not None:
            L = self.block_len
            if symbol_gen is None:
                raise MXNetError(
                    f"DecodeEngine({name!r}): the graph decodes by blocks "
                    f"of {L} positions, and the program of one block "
                    "needs symbol_gen= (a step_len -> per-slot decode "
                    "symbol factory)")
            off = [w for w in self.window_lens + [self.capacity] if w % L]
            if off:
                raise MXNetError(
                    f"DecodeEngine({name!r}): the graph decodes by blocks "
                    f"of {L} positions, which divide neither window "
                    f"lengths nor capacity {off}: every dispatch is whole "
                    "blocks from a block's edge (choose a prefill chunk "
                    f"and a capacity that are multiples of {L})")
            self.window_lens = sorted({L, *self.window_lens} - {1})
        self._window_mods = {}               # (rung, S) -> Module
        if self.window_lens:
            if symbol_gen is None:
                raise MXNetError(
                    f"DecodeEngine({name!r}): window_lens="
                    f"{self.window_lens} needs symbol_gen= (a "
                    "step_len -> per-slot decode symbol factory)")
            self._build_windows(symbol_gen, compute_dtype,
                                logger or log)
        for family, n in self.state_bytes.items():
            _telemetry.gauge("serve.decode.state.bytes", model=self.name,
                             family=family).set(n)

    def _build_windows(self, symbol_gen, compute_dtype, logger):
        """Every rung's window modules: for each length the graph that
        ``symbol_gen`` builds and, where the graph is a fed one and the
        rung's rows would halve, its packed form beside it
        (``packed_window``: the same graph with a row budget that
        follows from the rung and the length) - two programs over the
        same cells, of which the rung's driver launches the one that a
        dispatch's ``fed`` fits."""
        from ..module import Module
        from ..models.transformer import packed_window

        def bind(symbol, rung, S):
            mod = Module(symbol, data_names=list(self.data_names),
                         label_names=[], logger=logger,
                         context=self._context,
                         compute_dtype=compute_dtype)
            base = self._bm._buckets[rung]
            mod.bind(self._provide_data(rung, S), label_shapes=None,
                     for_training=False, shared_module=base)
            b_aux = base._exec_group.executor.aux_dict
            for nm, cell in mod._exec_group.executor.aux_dict.items():
                if b_aux.get(nm) is not cell:
                    raise MXNetError(
                        f"DecodeEngine({self.name!r}): window "
                        f"step_len={S} did not share aux cell "
                        f"{nm!r} with the rung-{rung} decode "
                        "module — symbol_gen must rebuild the SAME "
                        "graph (names, capacity, slot count) at a "
                        "different step_len")
            return mod

        for rung in self.ladder:
            for S in self.window_lens:
                symbol = symbol_gen(S)
                mod = self._window_mods[(rung, S)] = bind(symbol, rung, S)
                # every row of a block's own program is read
                form = None if S == self.block_len \
                    else packed_window(symbol, rung)
                if form is not None and self.block_len \
                        and form[1] % self.block_len:
                    raise MXNetError(
                        f"DecodeEngine({self.name!r}): the packed window "
                        f"of {S} rows a slot on rung {rung} has a budget "
                        f"of {form[1]} rows, which blocks of "
                        f"{self.block_len} positions do not divide")
                if form is not None:
                    form = (bind(form[0], rung, S), form[1])
                    self._window_mods[(rung, S, "packed")] = form[0]
                self._drivers[rung].add_window(S, mod, packed=form)

    def _note_params(self, arg_params):
        """What the binding holds, set once at bind: the bytes of the
        parameter cells by dtype (every rung and window shares the
        leader's: gauge ``serve.decode.params.bytes``) and how many of
        the parameters handed over were wider than their cell, cast
        once as they were stored (counter
        ``serve.decode.params.narrowed``)."""
        exe = self._bm._leader._exec_group.executor
        handed = arg_params or {}
        self.params_bytes, self.params_narrowed = {}, 0
        for nm, cell in exe.arg_dict.items():
            if nm in self.data_names:
                continue
            dt = cell.dtype
            self.params_bytes[str(dt)] = \
                self.params_bytes.get(str(dt), 0) + cell.size * dt.itemsize
            given = getattr(handed.get(nm), "dtype", None)
            if given is not None and jnp.issubdtype(given, jnp.floating) \
                    and jnp.dtype(given).itemsize > dt.itemsize:
                self.params_narrowed += 1
        for dt, n in self.params_bytes.items():
            _telemetry.gauge("serve.decode.params.bytes",
                             model=self.name, dtype=dt).set(n)
        _telemetry.counter("serve.decode.params.narrowed",
                           model=self.name).inc(self.params_narrowed)

    def _provide_data(self, slots, step=1):
        descs = [DataDesc("data", (slots, step), np.int32)]
        if self.pos_embed == "learned":
            # whole numbers like the tokens: a float cell is cast to the
            # compute width at graph entry, and bfloat16 holds no odd
            # position past 256 (a program in which the compiler drops
            # that cast reads the right row; a packed window, which
            # copies the positions, reads its neighbour's)
            descs.append(DataDesc("pos_ids", (slots, step), np.int32))
        descs.append(DataDesc("fed", (slots,), np.int32))
        return descs

    @property
    def positional(self):
        """Is the decode state a row per position (the drivers'
        ``positional``)? Rewinding to an arbitrary position, prefix
        reuse by row copy and speculative rollback rest on it."""
        return self._drivers[self.ladder.max].positional

    @property
    def state_bytes(self):
        """The per-slot state's bytes by family (``"cursor"``,
        ``"rows"``, ``"ring"``, ...), every rung's pools together: gauge
        ``serve.decode.state.bytes``."""
        out = {}
        for drv in self._drivers.values():
            for family, n in drv.state_bytes.items():
                out[family] = out.get(family, 0) + n
        return out

    def driver(self, rung):
        """The rung's ``BatchedKVCacheDecoder``."""
        return self._drivers[rung]

    def window_budget(self, rung, step_len):
        """The rows that one ``step_len`` window of ``rung`` slots may
        feed between them: R where the rung has a packed program for
        that length (``BatchedKVCacheDecoder.window_budget``), None
        where a window is every slot's ``step_len`` rows."""
        return self._drivers[rung].window_budget(step_len)

    # ------------------------------------------------------------- warmup
    def warmup(self, clock, rows=False):
        """Compile every slot rung's S=1 program AND every window
        program, each with its select program behind it (two steps
        each: first pays the traces, second measures steady state on
        ``clock`` the way an iteration runs it - step, select, token
        ids on the host - and one more of each length fed the ids of
        the step before from the device, the form of a dispatch that
        the scheduler launches ahead: an S=1 step fed them as they lie
        there, and a step of every length fed the host's tokens with
        the riders' first column merged in from the chip,
        ``drv.merge_tokens``), pin them all, record the compile
        delta.
        Where a window has a packed program (``window_budget``) that is
        the one a scheduler dispatches, and the one warmed: fed as a
        serving window is, one slot its whole chunk and a token for
        each other. The whole-window program of that rung stays bound
        and compiles at its first direct call (``step`` without
        ``fed``, or fed past the budget).
        Warmup garbage stays harmless: afterwards every driver slot is
        free, every cursor is rewound to 0, and a join rewinds again.
        Those rewinds also compile each rung's cursor program, whose
        shapes are the rung's whatever rows a call names, so no later
        ``join`` or ``rewind_many`` compiles. ``rows`` (a scheduler
        with a prefix store) also compiles each rung's row capture and
        restore programs, whose shapes are the rung's too whatever
        length a join has."""
        mark = _progcache.compile_count()
        if self.block is not None:
            return self._warmup_blocks(clock, rows, mark)
        for rung in self.ladder:
            drv = self._drivers[rung]
            last = np.zeros(rung, np.int32)

            def launch(tokens, fed=None):
                _rows, ids, nxt = drv.select_rows(
                    drv.step(tokens, fed=fed), last)
                drv.release_outputs()   # no rung keeps warm-up's logits
                drv.moe_stats_begin()
                return ids, nxt

            def step_ids(tokens, fed=None):
                return np.asarray(launch(tokens, fed)[0])

            zeros = np.zeros((rung, 1), np.int32)
            riders = np.arange(rung) > 0
            step_ids(zeros)                      # trace + compile
            t0 = clock.now()
            ids, nxt = launch(zeros)             # steady state
            np.asarray(ids)
            self.exec_est[rung] = max(0.0, clock.now() - t0)
            # a dispatch that runs ahead takes its tokens from the chip:
            # as they lie there, or merged into the host's
            _, nxt = launch(nxt)
            step_ids(drv.merge_tokens(zeros, nxt, riders))
            for S in drv.window_lens:
                wz = np.zeros((rung, S), np.int32)
                fed = None if drv.window_budget(S) is None \
                    else np.asarray([S] + [1] * (rung - 1))
                # rewind first so that even a tiny cache has room for
                # the window: warm-up runs the write steady state runs
                # (a slot with no room for S rows writes nothing)
                drv.rewind_many(list(range(rung)), [0] * rung)
                step_ids(wz, fed)                # trace + compile
                drv.rewind_many(list(range(rung)), [0] * rung)
                t0 = clock.now()
                ids, nxt = launch(wz, fed)       # steady state
                np.asarray(ids)
                self.exec_est[(rung, S)] = max(0.0, clock.now() - t0)
                drv.rewind_many(list(range(rung)), [0] * rung)
                step_ids(drv.merge_tokens(wz, nxt, riders), fed)
            if rows and drv.positional:
                drv.warm_rows()
            drv.active[:] = False
            drv.rewind_many(list(range(rung)), [0] * rung)
        return self._warmed(mark)

    def _warmed(self, mark):
        self._pin_programs()
        self._warm_mark = _progcache.compile_count()
        self._warm_backend_mark = _telemetry.core.backend_compiles()
        self._warm_launch_mark = self._launch_counts()
        self.warmup_compiles = self._warm_mark - mark
        return dict(self.exec_est)

    def _warmup_blocks(self, clock, rows, mark):
        """``warmup`` of an engine that decodes by blocks: every rung's
        block program with ``denoise_select`` behind it and the cursors
        put back, as a feed that keeps nothing runs, and every other
        window program as a prefill window is launched - one slot its
        whole chunk, nobody else a row (the packed form where the rung
        has one). A block dispatch that runs ahead takes ids, mask and
        who goes back from the chip: one more of the block's launches
        fed that way (``merge_block``, and the step, the decision and
        the cursor program over what it leaves there). The S = 1
        program, which serves no request, stays bound and compiles at
        its first direct call."""
        L = self.block_len
        for rung in self.ladder:
            drv = self._drivers[rung]
            slots = list(range(rung))
            for S in drv.window_lens:
                wz = np.zeros((rung, S), np.int32)
                fed = np.asarray([S] + [0] * (rung - 1)) \
                    if S != L and drv.window_budget(S) is not None else None

                def launch(chip=None):
                    drv.rewind_many(slots, [0] * rung)
                    tokens, left, back = wz, np.ones((rung, S), bool), None
                    if chip is not None:    # the block's, from the chip
                        tokens, left, back = drv.merge_block(
                            tokens, left, chip, np.arange(rung) > 0)
                    out = drv.step(tokens, fed=fed)
                    drv.release_outputs()
                    if S != L:
                        state = drv.select_rows(
                            out, np.zeros(rung, np.int32))[1]
                    else:
                        state = drv.denoise_select(
                            out, tokens, left, np.ones(rung),
                            np.full(rung, np.inf))
                        if back is not None:
                            drv.rewind_many(slots, [0] * rung, where=back)
                            drv.kept(())    # whoever did goes to 0 below
                    drv.moe_stats_begin()
                    np.asarray(state)           # waits for the device
                    return state

                launch()                         # trace + compile
                t0 = clock.now()
                state = launch()                 # steady state
                self.exec_est[(rung, S)] = max(0.0, clock.now() - t0)
                if S == L:
                    launch(chip=state)           # as one launched ahead
            if rows and drv.positional:
                drv.warm_rows()
            drv.active[:] = False
            drv.rewind_many(slots, [0] * rung)
        return self._warmed(mark)

    def note_exec(self, rung, seconds):
        prev = self.exec_est.get(rung)
        self.exec_est[rung] = seconds if prev is None else \
            0.7 * prev + 0.3 * seconds

    def exec_estimate(self, rung):
        if rung in self.exec_est:
            return self.exec_est[rung]
        known = list(self.exec_est.values())
        return max(known) if known else 0.0

    def compiles_since_warmup(self):
        """Traces that entered the process-wide program cache since
        warm-up (None before it): a new program of ``Module``/
        ``Executor``. The drivers' cursor and select programs (compiled
        during warm-up) and ``migrate``'s eager one-operation copies never
        pass through that cache; ``backend_compiles_since_warmup``
        sees those too."""
        if self._warm_mark is None:
            return None
        return _progcache.compile_count() - self._warm_mark

    def backend_compiles_since_warmup(self):
        """XLA backend compiles (or persistent-cache reads) of this
        PROCESS since warm-up, whoever asked for them (None before
        it): ``jax.monitoring``'s count, the ``xla.compile`` ring
        records name each one."""
        if self._warm_backend_mark is None:
            return None
        return _telemetry.core.backend_compiles() - self._warm_backend_mark

    @staticmethod
    def _launch_counts():
        return (_telemetry.metrics.generation(),
                {nm: getattr(_telemetry.get_metric(nm), "value", 0)
                 for nm in _LAUNCH_COUNTERS})

    def launch_work_since_warmup(self):
        """What the forwards of this PROCESS did on the way to their
        programs since warm-up (None before it): inputs ``_load_batch``
        converted or placed (``io.load_batch.puts``) and took as they
        were (``.aliased``), keys ``Executor.forward`` drew
        (``executor.rng.draws``). A serving step puts nothing and draws
        nothing. Counted only while ``telemetry.enabled()``: with it
        off all three stand still."""
        if self._warm_launch_mark is None:
            return None
        gen, marks = self._warm_launch_mark
        now_gen, now = self._launch_counts()
        return {nm: n - (marks[nm] if gen == now_gen else 0)
                for nm, n in now.items()}

    def program_keys(self):
        keys = []
        # (the S = 1 program of an engine that decodes by blocks serves
        # no request and is not compiled at warm-up)
        for rung, mod in () if self.block is not None \
                else self._bm._buckets.items():
            key = mod._exec_group.executor.program_cache_key("fwd_infer")
            if key is not None:
                keys.append(key)
        for at, mod in self._window_mods.items():
            if at + ("packed",) in self._window_mods:
                continue        # warmed as its packed form (``warmup``)
            key = mod._exec_group.executor.program_cache_key("fwd_infer")
            if key is not None:
                keys.append(key)
        return keys

    def _pin_programs(self):
        for key in self.program_keys():
            if not _progcache.pin(key):
                log.warning(
                    "decode %r: rung program not resident at pin time "
                    "(cache capacity too small for the slot ladder? "
                    "MXNET_PROGRAM_CACHE_SIZE)", self.name)

    def programs_resident(self):
        keys = self.program_keys()
        return all(_progcache.contains(k) for k in keys) if keys else True

    # ---------------------------------------------------------- migration
    def migrate(self, src_rung, dst_rung, pairs):
        """Carry live slots between rung pools: for every (src_row,
        dst_row) pair, the slot's state - every cell of every family
        the graph's ops declare (K/V rows and cursor; or the open
        window's rows, the summaries and the cursor) - copies from the
        ``src_rung`` aux arrays into ``dst_rung``'s, and the host
        mirrors follow. Eager per-row gathers/scatters — nothing lands
        in the program cache, so rung switches keep the zero-compile
        contract."""
        if src_rung == dst_rung:
            return
        sdrv, ddrv = self._drivers[src_rung], self._drivers[dst_rung]
        d_exe = self._bm._buckets[dst_rung]._exec_group.executor
        ddrv.active[:] = False
        if pairs:
            si = np.asarray([p[0] for p in pairs])
            di = np.asarray([p[1] for p in pairs])
            for nm, cell in sdrv.slot_cells():
                dcell = d_exe.aux_dict[nm]
                dcell._set(dcell.asjax().at[di].set(cell.asjax()[si]))
            for s_row, d_row in pairs:
                ddrv.pos[d_row] = sdrv.pos[s_row]
                ddrv.active[d_row] = True
        sdrv.active[:] = False


#: ``serve.decode.<name>`` counters of the S > 1 window dispatches, from
#: the plan (no fetch): the dispatches themselves (beside them
#: ``runahead.windows``, those launched before their predecessor's ids
#: were on the host), the slots fed at least one row, and those fed
#: exactly one - a decoding slot riding a window in which another
#: prefills, its other S - 1 rows pads. riding / fed is the traffic's,
#: whatever kernel serves it. Then the rows that were real tokens, and
#: the rows that the launched program ran its row-wise operations over
#: (slots x S, or the budget R of a packed program): real / program is
#: the share of a window's dense work that was not pads. Last the rows
#: its head ran over - the final norm and the product with the
#: vocabulary: the same, but of a packed program each slot's last fed
#: row alone (``slots``) - so head / program says how often the form
#: that selects before the head engaged. And the copies of rows in the
#: launched program (its ``pack_rows`` / ``unpack_rows`` nodes under a
#: budget; none in a whole-window program) beside those of them that
#: hold no loop - all where a slot's rows are one chunk, ``step_len``
#: 64, and none at 256 or more (``ops/rows.py``): static / copy says
#: which lowering the served windows took, from the graph alone
_WINDOW_COUNTERS = ("window.dispatches", "window.fed_slots",
                    "window.riding_slots", "window.real_rows",
                    "window.program_rows", "window.head_rows",
                    "window.copy_sites", "window.static_copy_sites")


#: ``serve.decode.<name>`` counters of an engine that decodes by blocks:
#: a slot's block fed once (whether or not the feed was kept), blocks
#: committed, positions decided, rows fed whose keys and values were
#: not kept (``L`` a feed that is taken back), and positions denoised
#: past a request's length, which nobody is delivered
_DIFFUSION_COUNTERS = ("diffusion.feeds", "diffusion.blocks",
                       "diffusion.decided", "diffusion.rows_dropped",
                       "diffusion.undelivered")


#: what one dispatch's launches left on the device (``_launch``): the
#: whole output (the speculative path alone), the selected rows, their
#: ids, the ids as the next S=1 step's token input, the ``moe_stats``
#: stack
_Launched = collections.namedtuple(
    "_Launched", "out picked ids tokens routed")


class _Dispatch:
    """One dispatch from its plan to its commit: what the plan fixed
    (``mode``, ``S``, ``meta``: ``(row, seq[, n_fed])`` of the slots it
    feeds; the host's ``tokens``, each slot's ``last`` fed row, ``fed``,
    and ``feed``, the slots that sample at it; of a dispatch launched
    ``ahead`` also ``chip``, the slots whose one token is the id that
    its predecessor left on the chip - None where nobody's is, and
    ``tokens`` None where everybody's is and those ids are the tokens
    as they lie), what ``_launch`` left on the
    device (``launched``), and its own clock: ``t0`` where its step
    starts (the plan's first reading, or for a dispatch launched
    ``ahead`` the moment its predecessor's ids were on the host),
    ``plan_s`` and ``phases``. A dispatch of one block a slot
    (``block``: the block's length; 0 of any other) carries instead of
    ``last`` / ``feed`` what ``denoise_select`` takes -
    ``undecided``, ``quota``, ``threshold`` - and ``back``, the rows
    and cursors of the slots whose feed keeps nothing; its ``meta`` is
    ``(row, seq, L)`` and ``kinds`` says of each row whether its feed
    is ``"tentative"``, a ``"commit"`` or a ``"prefill"`` of prompt
    tokens. Of one launched ``ahead``, ``chip`` names the slots whose
    feed before it was tentative: their ids, their mask and whether
    this feed keeps its rows are what that feed's ``denoise_select``
    leaves on the chip (kind ``"chip"``, among ``back`` in case),
    until the commit of the feed before settles each as a commit or
    tentative (``_settle_block``); ``blocks`` holds the blocks that
    start at this dispatch, each its sequence's once that commit has
    closed the block before."""

    __slots__ = ("mode", "S", "meta", "tokens", "last", "fed", "feed",
                 "chip", "want_rows", "n_active", "shared_sid", "t0",
                 "plan_s", "phases", "ahead", "launched", "block",
                 "undecided", "quota", "threshold", "back", "kinds",
                 "blocks")

    def __init__(self, mode, S, t0, ahead=False):
        self.mode, self.S, self.t0, self.ahead = mode, S, t0, ahead
        self.meta = []
        self.tokens = self.last = self.fed = self.feed = self.chip = None
        self.want_rows = False
        self.n_active = 0
        self.shared_sid = self.launched = None
        self.block = 0
        self.undecided = self.quota = self.threshold = None
        self.back, self.kinds, self.blocks = ([], []), {}, {}
        self.plan_s = 0.0
        self.phases = {"denoise": 0.0, "decided": 0,
                       "dispatch": 0.0, "fetch": 0.0, "bytes": 0,
                       "stage": 0.0, "launch": 0.0, "select": 0.0,
                       "ids": 0.0, "program_rows": 0, "head_rows": 0,
                       "copy_sites": 0, "static_copy_sites": 0,
                       "reads": collections.Counter()}


class DecodeScheduler:
    """Iteration-level continuous batching over one ``DecodeEngine``.

    ``submit(prompt)`` admits a sequence (``QueueFullError`` past
    ``MXNET_SERVE_DECODE_MAX_QUEUE``) and returns a streaming
    ``DecodeHandle``. Each scheduler iteration retires finished
    sequences (EOS / max-new / deadline / per-slot overflow), admits
    queued ones into free slots (growing the rung when the ladder
    allows), migrates live slots on rung switches, then advances every
    slot through the rung's pinned programs and streams the sampled
    tokens. Sampling is per request (``SamplingParams``; default
    greedy-argmax).

    Fast paths (each armed only when its programs were built at engine
    construction, so steady state never compiles): ``prefill_chunk``
    S>1 window dispatches while any slot is prefilling (decoding slots
    ride along with one real token + pads, fed 1);
    ``draft_engine`` + ``spec_k`` speculative iterations when every
    active slot is in steady state (K draft proposals, one S=K target
    verify, exact rejection, cursor rollback on both engines);
    ``prefix_store`` joins at cursor C on ``submit(prefix_id=...)``
    hits and snapshots cold prefixes when their prefill completes.

    Over an engine that decodes by blocks (``engine.block``) a step
    yields 0 to ``L`` tokens a slot (``_plan_block``,
    ``_commit_block``): every cursor stays on a block's edge, a window
    carries prefill chunks alone and waits for a commit on both sides,
    a block dispatch runs ahead of the one before it
    (``_plan_block_ahead``), a draft engine and a request that is not
    greedy are refused by name.
    """

    def __init__(self, engine, clock=None, max_queue=None,
                 default_max_new=None, logger=None, draft_engine=None,
                 prefill_chunk=None, spec_k=None, prefix_store=None):
        self.engine = engine
        self.draft = draft_engine
        self._clock = clock if clock is not None else MonotonicClock()
        self._max_queue = max_queue if max_queue is not None else \
            _env_int("MXNET_SERVE_DECODE_MAX_QUEUE", 256)
        self._default_max_new = default_max_new if default_max_new \
            is not None else _env_int("MXNET_SERVE_DECODE_MAX_NEW", 64)
        self.logger = logger or log

        self._block = engine.block          # None: one token a step
        if self.draft is not None and (self._block is not None
                                       or self.draft.block is not None):
            L = (self._block or self.draft.block)["block_length"]
            raise MXNetError(
                f"decode {engine.name!r}: speculative decoding "
                "(draft_engine / spec_k) proposes one token a step and "
                "verifies a window of them, and an engine that decodes "
                f"by blocks of {L} positions decides a block's positions "
                "in no such order")
        if self.draft is not None:
            if list(self.draft.ladder.sizes) != list(engine.ladder.sizes):
                raise MXNetError(
                    f"draft engine ladder {self.draft.ladder.sizes} "
                    f"must match the target's {engine.ladder.sizes} "
                    "(slots mirror 1:1)")
            if self.draft.capacity < engine.capacity:
                raise MXNetError(
                    f"draft cache capacity {self.draft.capacity} < "
                    f"target capacity {engine.capacity}: the draft "
                    "tracks the same stream")
        if not engine.positional:
            # the state behind a closed window is summaries, the rows
            # a ring has written over are gone: no cursor move brings
            # them back
            families = sorted(engine.state_bytes)
            if self.draft is not None:
                raise MXNetError(
                    f"decode {engine.name!r}: speculative decoding "
                    "(draft_engine / spec_k) rolls the cursor back over "
                    "rejected drafts, and this decoder's state "
                    f"(families {families}) cannot be rewound across a "
                    "closed window or behind what a ring holds")
            if prefix_store is not None:
                raise MXNetError(
                    f"decode {engine.name!r}: a prefix_store reuses a "
                    "prompt's cache by copying a row per position, and "
                    f"this decoder's state (families {families}) is a "
                    "window of exact rows beside summaries, or rings "
                    "(reuse needs a snapshot of the state at a window "
                    "boundary)")
        chunk = int(prefill_chunk if prefill_chunk is not None
                    else default_prefill_chunk())
        chunk = min(chunk, engine.capacity)
        usable = set(engine.window_lens)
        if self.draft is not None:
            usable &= set(self.draft.window_lens)
        self.prefill_chunk = chunk if chunk > 1 and chunk in usable \
            else 1
        k = int(spec_k if spec_k is not None else default_spec_k())
        self.spec_k = 0
        if self.draft is not None:
            if k < 2 or k not in set(engine.window_lens):
                raise MXNetError(
                    f"speculative decoding armed (draft engine given) "
                    f"but the target has no step_len={k} verify window "
                    f"(windows: {engine.window_lens}); build the "
                    "engine with spec_k in window_lens")
            self.spec_k = k
        self.prefix_store = prefix_store
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rollbacks = 0

        # reentrant: completion/token callbacks run with the scheduler
        # lock held and may legitimately submit a follow-up sequence
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._queue = []
        self._rung = self.engine.ladder.sizes[0]
        self._slots = [None] * self._rung
        self._thread = None
        self._running = False
        self.iterations = 0
        self.migrations = 0
        self._iter_handles = None       # (registry generation, handles)
        # the last clock read of the iteration before (its rewind's end),
        # None where none ran straight before; the iterating thread's own
        self._turn_from = None
        # the dispatch launched before its predecessor's ids were on the
        # host, until the next iteration commits it; the iterating
        # thread's own too
        self._ahead = None
        # draft first: the target's post-warmup compile mark is the
        # zero-compile gate stats() reports, so it must be taken LAST
        if self.draft is not None:
            with _telemetry.span("serve.decode.warmup",
                                 model=self.draft.name):
                self.draft.warmup(self._clock,
                                  rows=prefix_store is not None)
            # a verify window feeds every slot all K rows, which is the
            # whole-window program's to run: where the engine's warm-up
            # compiles a packed form in its place, compile it here
            for rung in engine.ladder:
                drv = engine.driver(rung)
                if drv.window_budget(self.spec_k) is not None:
                    drv.step(np.zeros((rung, self.spec_k), np.int32))
                    drv.release_outputs()
                    drv.rewind_many(list(range(rung)), [0] * rung)
        with _telemetry.span("serve.decode.warmup",
                             model=self.engine.name):
            est = self.engine.warmup(self._clock,
                                     rows=prefix_store is not None)
        if self.draft is not None:
            # the target's warmup compiles landed after the draft's
            # mark; refresh it so BOTH gates read 0 in steady state
            self.draft._warm_mark = _progcache.compile_count()
            self.draft._warm_backend_mark = self.engine._warm_backend_mark
        self.logger.info(
            "decode %r warmed — slot ladder %s, windows %s, "
            "%d compiles, step est %s",
            self.engine.name, self.engine.ladder.sizes,
            self.engine.window_lens, self.engine.warmup_compiles,
            {r: f"{s * 1e3:.2f}ms" for r, s in est.items()})
        self._gauge("slots").set(self._rung)
        self._gauge("active").set(0)
        self._gauge("occupancy").set(0.0)
        self._gauge("queue.depth").set(0)

    def _gauge(self, key):
        return _telemetry.gauge(f"serve.decode.{key}",
                                model=self.engine.name)

    def _counter(self, key):
        return _telemetry.counter(f"serve.decode.{key}",
                                  model=self.engine.name)

    def _iter_metrics(self):
        """The registry handles every iteration writes, looked up once
        (a lookup is a lock and a key tuple each) and again after the
        registry resets."""
        gen = _telemetry.metrics.generation()
        if self._iter_handles is None or self._iter_handles[0] != gen:
            handles = {k: self._counter(k) for k in
                       ("iterations", "tokens", "prefill.chunks",
                        "fetch.bytes", "sample.device", "sample.host",
                        "runahead.launched", "runahead.dropped",
                        "runahead.windows")
                       + _WINDOW_COUNTERS
                       # a block engine's: its own, and the dispatches
                       # of one block a slot that were launched ahead
                       + (_DIFFUSION_COUNTERS + ("runahead.blocks",)
                          if self._block else ())}
            # what the graph's ops count of a dispatch (the driver's
            # ``read_counts``: ``OpDef.state_reads``)
            handles.update({k: self._counter(k) for k, _field in
                            self.engine.driver(self._rung)
                            .read_counts.values() if k})
            handles.update({k: self._gauge(k) for k in
                            ("active", "occupancy", "queue.depth")})
            handles["step.seconds"] = _telemetry.histogram(
                "serve.decode.step.seconds", model=self.engine.name)
            handles["compiles_since_warmup"] = _telemetry.gauge(
                "serve.program_cache.compiles_since_warmup")
            self._iter_handles = (gen, handles)
        return self._iter_handles[1]

    # ------------------------------------------------------------ admission
    def submit(self, prompt, max_new_tokens=None, eos_id=None,
               deadline_ms=None, trace=None, sampling=None,
               prefix_id=None):
        """Admit one sequence: ``prompt`` is a 1-D int id sequence
        (1 <= len <= cache capacity). ``max_new_tokens`` caps
        generation (``MXNET_SERVE_DECODE_MAX_NEW`` default); ``eos_id``
        retires the sequence when sampled (not emitted);
        ``deadline_ms`` (relative to now) retires it mid-decode with a
        partial result and ``finish_reason="deadline"``. ``sampling``
        is a ``SamplingParams`` (default greedy-argmax; replaying the
        same params + prompt reproduces the token stream byte for
        byte). ``prefix_id`` names a shared prompt prefix for the
        prefix store: a hit joins at cursor C with donated cache rows,
        a miss prefills cold and snapshots the prompt's rows for the
        next submit. Returns the streaming ``DecodeHandle``."""
        prompt = np.asarray(prompt, np.int64).reshape(-1)
        if prompt.size < 1:
            raise MXNetError("empty prompt")
        if prompt.size > self.engine.capacity:
            raise MXNetError(
                f"prompt of {prompt.size} tokens exceeds the decode "
                f"cache capacity {self.engine.capacity}")
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self._default_max_new)
        if max_new < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        self._check_sampling(sampling)
        now = self._clock.now()
        deadline = None if deadline_ms is None \
            else now + deadline_ms / 1000.0
        tr = trace
        if tr is None and _trace.sample():
            tr = _trace.new_trace(session=True)
        seq = _Sequence(prompt, max_new, eos_id, now, deadline, trace=tr,
                        sampling=sampling, prefix_id=prefix_id,
                        block_len=self.engine.block_len or 0)
        if tr is not None:
            seq.root_sid = _trace.next_span_id()
            if tr.root is None:
                tr.root = seq.root_sid
            if tr.start_s is None:
                tr.start_s = now
        with self._cond:
            if len(self._queue) >= self._max_queue:
                exc = QueueFullError(
                    f"decode {self.engine.name!r}: queue depth "
                    f"{len(self._queue)} at MXNET_SERVE_DECODE_"
                    f"MAX_QUEUE={self._max_queue}")
                if tr is not None:
                    exc.trace_id = tr.trace_id
                _telemetry.counter("serve.rejected",
                                   model=self.engine.name).inc()
                raise exc
            self._queue.append(seq)
            depth = len(self._queue)
            self._cond.notify_all()
        self._counter("requests").inc()
        self._gauge("queue.depth").set(depth)
        return seq.handle

    def _check_sampling(self, sampling):
        """Refuse by name what the engine cannot serve: of an engine
        that decodes by blocks a request that is not greedy (a block's
        positions are decided on the device, by confidence) or whose
        denoising parameters the block has no room for; of any other a
        request that sets denoising parameters."""
        denoising = sampling is not None and sampling.denoises
        if self._block is None:
            if denoising:
                raise MXNetError(
                    f"decode {self.engine.name!r}: {sampling!r} sets "
                    "denoising parameters, and this engine decodes one "
                    "token a step (they are a request's of a model that "
                    "decodes by blocks)")
            return
        L = self.engine.block_len
        if sampling is not None and not sampling.greedy:
            raise MXNetError(
                f"decode {self.engine.name!r}: {sampling!r} is not greedy, "
                f"and this engine decodes by blocks of {L} positions, "
                "which it decides on the device by their confidence "
                "(argmax; temperature 0)")
        if denoising and sampling.denoising_steps is not None \
                and not 1 <= sampling.denoising_steps <= L:
            raise MXNetError(
                f"decode {self.engine.name!r}: denoising_steps "
                f"{sampling.denoising_steps} of a block of {L} positions "
                f"(1 to {L}: a feed decides at least one)")

    def _denoising(self, seq):
        """``(steps, threshold)`` a sequence's blocks are denoised
        with: the request's where it set them, else the graph's; a
        static schedule never looks at the threshold."""
        given, graph = seq.sampling, self._block
        steps = given.denoising_steps or graph["denoising_steps"]
        remasking = given.remasking or graph["remasking"]
        threshold = graph["confidence_threshold"] \
            if given.confidence_threshold is None \
            else given.confidence_threshold
        return int(steps), float(threshold) \
            if remasking == "low_confidence_dynamic" else float("inf")

    # ----------------------------------------------------------- scheduling
    def _active(self):
        return [s for s in self._slots if s is not None]

    def _finish(self, seq, reason=None, error=None, now=None):
        """Complete a sequence's handle and free its slot (caller holds
        the lock)."""
        seq.finish_reason = reason
        if seq.slot is not None:
            self.engine.driver(self._rung).leave(seq.slot)
            if self.draft is not None:
                self.draft.driver(self._rung).leave(seq.slot)
            self._slots[seq.slot] = None
            seq.slot = None
            self._counter("leaves").inc()
        if seq.trace is not None:
            _trace.record(
                seq.trace, "serve.decode.sequence", seq.arrival,
                now if now is not None else self._clock.now(),
                span_id=seq.root_sid, model=self.engine.name,
                prompt=len(seq.prompt), generated=len(seq.generated),
                finish=reason if error is None else
                type(error).__name__)
            if error is not None:
                error.trace_id = seq.trace.trace_id
        self._counter("errors" if error is not None
                      else "responses").inc()
        if error is None:
            _telemetry.histogram(
                "serve.decode.request.latency.seconds",
                model=self.engine.name).observe(
                max(0.0, (now if now is not None else
                          self._clock.now()) - seq.arrival),
                exemplar=seq.trace.trace_id
                if seq.trace is not None else None)
        seq.handle._complete(error=error, now=now)

    def _switch_rung(self, target):
        """Migrate live slots into the ``target`` rung pool, compacting
        them into the lowest rows (caller holds the lock)."""
        pairs = []
        new_slots = [None] * target
        dst = 0
        for row, seq in enumerate(self._slots):
            if seq is None:
                continue
            pairs.append((row, dst))
            seq.slot = dst
            new_slots[dst] = seq
            dst += 1
        self.engine.migrate(self._rung, target, pairs)
        if self.draft is not None:
            self.draft.migrate(self._rung, target, pairs)
        self._rung = target
        self._slots = new_slots
        self.migrations += 1
        self._counter("migrations").inc()
        self._gauge("slots").set(target)

    def _admit_locked(self, now):
        """Retire expired queued requests, grow the rung if the backlog
        wants it, and fill free slots FIFO."""
        for seq in [s for s in self._queue
                    if s.deadline is not None and now > s.deadline]:
            self._queue.remove(seq)
            self._finish(seq, reason="deadline", now=now)
        if not self._queue:
            return
        want = min(len(self._active()) + len(self._queue),
                   self.engine.ladder.max)
        target = self.engine.ladder.bucket_for(max(want, 1))
        if target is not None and target > self._rung:
            self._switch_rung(target)
        drv = self.engine.driver(self._rung)
        for row in range(self._rung):
            if self._slots[row] is not None or not self._queue:
                continue
            seq = self._queue.pop(0)
            drv.join(row)
            if self.draft is not None:
                self.draft.driver(self._rung).join(row)
            seq.slot = row
            self._slots[row] = seq
            self._counter("joins").inc()
            self._counter("prompt_tokens").inc(len(seq.prompt))
            if seq.prefix_id is not None and \
                    self.prefix_store is not None:
                self._prefix_admit(row, seq, now)
            if seq.trace is not None:
                _trace.record(seq.trace, "serve.decode.queue.wait",
                              seq.arrival, now, parent=seq.root_sid,
                              slot=row)

    def _prefix_admit(self, row, seq, now):
        """Prefix-store hit test for one freshly joined sequence: on a
        hit the slot *joins at cursor C*, the longest head its prompt
        shares with the stored one (a head shorter than one dispatch is
        a miss) — the stored rows write back into its cache slice
        (bitwise what a cold prefill of those positions computes) and
        the cursor rewinds forward to C, so prefill starts at the first
        unshared token. ``serve.decode.prefix.join`` runs from before
        the rows' restore to after the rewind, with the bytes put to
        the device; ``serve.decode.prefix.joined_tokens`` counts C. A
        miss marks the sequence cold: its prompt rows snapshot into the
        store the iteration its prefill completes."""
        tags = ("target", "draft") if self.draft is not None \
            else ("target",)
        c, entry = self.prefix_store.lookup(
            seq.prefix_id, seq.prompt, tags=tags, least=self.prefill_chunk)
        if entry is not None and self._block is not None:
            # a join lands on a block's edge, rounded down (the rows of
            # a block are its whole block's; the rest is prefilled)
            c = c // seq.block_len * seq.block_len
            entry = entry if c else None
        if entry is None:
            seq.prefix_cold = True
            self._counter("prefix.misses").inc()
            return
        t_join = self._clock.now()
        with _telemetry.span("serve.decode.prefix.join"):
            drv = self.engine.driver(self._rung)
            put = drv.restore_rows(
                row, {nm: r[:, :c]
                      for nm, r in entry.payloads["target"].items()})
            drv.rewind(row, c)
            if self.draft is not None:
                ddrv = self.draft.driver(self._rung)
                put += ddrv.restore_rows(
                    row, {nm: r[:, :c]
                          for nm, r in entry.payloads["draft"].items()})
                ddrv.rewind(row, c)
        seq.fed = c
        self._counter("prefix.hits").inc()
        self._counter("prefix.joined_tokens").inc(c)
        if seq.trace is not None:
            _trace.record(seq.trace, "serve.decode.prefix.join",
                          t_join, self._clock.now(), parent=seq.root_sid,
                          slot=row, cursor=c, bytes=put)

    def _cursors(self, after=None):
        """``(row, seq, at, left)`` of every active slot (caller holds
        the lock): the stream position its next token is fed at and the
        stream tokens it has left to feed, as they stand (``seq.fed``,
        ``seq.remaining()``) or as the commit of the dispatch ``after``
        will leave them: ``at`` further by what ``after`` feeds the
        slot's owner (nothing where it is not in ``after.meta``, or
        joined a row that ``after`` feeds for somebody gone), and one
        token left, the id on its way, where the slot samples at
        ``after`` - unless that token is its last by length, which
        takes the slot out. Beside them the rows whose owner samples at
        ``after`` and stays, and the cursors of those who leave."""
        fed_by = {} if after is None else {
            row: n for row, seq, n in after.meta
            if self._slots[row] is seq}
        cursors, sampled, leaving = [], set(), []
        for row, seq in enumerate(self._slots):
            if seq is None:
                continue
            n = fed_by.get(row, 0)
            left = seq.remaining() - n
            if left == 0:       # samples at ``after``
                if len(seq.generated) + 1 >= seq.max_new:
                    leaving.append(seq.fed + n)
                    continue
                sampled.add(row)
            cursors.append((row, seq, seq.fed + n, left or 1))
        return cursors, sampled, leaving

    def _plan_dispatch(self, cursors):
        """Pick a dispatch's shape from ``cursors`` (``_cursors``;
        caller holds the lock): ``("window", S)`` — every active slot
        feeds up to S stream tokens (S = prefill chunk while anyone
        prefills and every live cursor has room, else 1) — or
        ``("spec", K)`` when speculation is armed and every active slot
        is in steady state with K positions of cache headroom on both
        engines."""
        ddrv = self.draft.driver(self._rung) if self.draft else None

        def room(S):
            return all(at + S <= self.engine.capacity
                       for _row, _seq, at, _left in cursors) and \
                (ddrv is None or not ddrv.overflowing(S))

        if any(left > 1 for _row, _seq, _at, left in cursors):
            S = self.prefill_chunk
            return "window", S if S > 1 and room(S) else 1
        if self.spec_k and ddrv is not None and room(self.spec_k):
            return "spec", self.spec_k
        return "window", 1

    def _plan_window(self, S, cursors):
        """``(row, seq, n)`` of every slot that this S-row dispatch
        feeds, ``n`` >= 1 stream tokens each, from ``cursors``
        (``_cursors``; caller holds the lock).
        Without a budget every active slot takes ``min(S, left)``.
        Where the engine has a packed program for this rung and length
        (``window_budget``: R rows between the slots; a fed engine
        alone), the window is planned inside it: decoding slots take
        their one token first, then the prefilling slots ``min(S,
        left, what is left of R)``, oldest admission first. A
        prefilling slot for which nothing is left is fed nothing this
        window and is not in the plan: the program leaves it where it
        is. R holds a whole chunk beside a token a slot, so the oldest
        prefilling slot always moves."""
        budget = None if S == 1 \
            else self.engine.window_budget(self._rung, S)
        if budget is None:
            return [(row, seq, min(S, left))
                    for row, seq, _at, left in cursors]
        room = budget - sum(left == 1 for _row, _seq, _at, left in cursors)
        plan = []
        # a sequence's id counts submissions, and admission is in order
        for row, seq, _at, left in sorted(cursors, key=lambda c: c[1].id):
            n = 1
            if left > 1:
                n = min(S, left, room)
                room -= n
            if n:
                plan.append((row, seq, n))
        return sorted(plan, key=lambda entry: entry[0])

    def _fill_window(self, d, cursors, now, chip=()):
        """Fill the window (or S=1) dispatch ``d`` from the plan of its
        length over ``cursors`` (caller holds the lock): the tokens,
        each slot's last fed row, ``fed``, ``feed``, whether a row has
        to come to the host, ``meta``. A slot among ``chip`` takes as
        its one token the id that the dispatch before leaves on the
        chip (``_launch`` merges it in): the host's entry stays 0."""
        at = {row: (pos, left) for row, _seq, pos, left in cursors}
        d.tokens = np.zeros((self._rung, d.S), np.int32)
        # each slot's last fed row (0 where nobody owns the row), and
        # whether a slot that samples now needs the row itself on the
        # host: a greedy one needs its id only
        d.last = np.zeros(self._rung, np.int32)
        # the slots that sample at this dispatch: the ids a dispatch
        # behind it is fed from the chip (a row in mid-prompt has an
        # argmax too, which nobody may be fed)
        d.feed = np.zeros(self._rung, bool)
        # the decoder advances each slot by its real tokens alone: a
        # row nobody owns, or one the budget left out, is fed nothing
        d.fed = np.zeros(self._rung, np.int32)
        for row, seq, n in self._plan_window(d.S, cursors):
            pos, left = at[row]
            if row not in chip:
                d.tokens[row, :n] = seq.window(pos, n)
            d.last[row] = n - 1
            d.feed[row] = n == left
            d.fed[row] = n
            if n == left and not seq.sampling.greedy:
                d.want_rows = True
            if seq.first_dispatch_at is None:
                seq.first_dispatch_at = now
            d.meta.append((row, seq, n))
        d.n_active = len(cursors)
        if any(seq.trace is not None for _row, seq, _at, _left in cursors):
            d.shared_sid = _trace.next_span_id()

    def _launch(self, drv, tokens, phases, t=None, last=None, rows=False,
                fed=None, feed=None, chip=None):
        """One dispatch's launches, ``serve.decode.iter.dispatch``:
        ``drv.step`` (staging and launch), where ``last`` names each
        slot's last fed row ``drv.select_rows`` behind it, which picks
        those rows and takes their argmax on the device, and where the
        graph routes the stack of its ``moe_stats`` cells
        (``drv.moe_stats_begin``): everything of this dispatch that the
        next step's launch would take away, so that another dispatch
        may be launched before this one is fetched. ``tokens`` are the
        host's, or the ids of the dispatch before as they lie on the
        chip, or with ``chip`` (those ids, and whose they are) the
        host's with column 0 of those slots taken from the chip
        (``drv.merge_tokens``, one small launch in front of the step);
        ``feed`` says whose id this dispatch's own device tokens
        carry (``select_rows``). ``fed`` (the real tokens of each
        slot) rides in the same put as the tokens.
        Adds the duration to ``phases["dispatch"]`` on the scheduler's
        clock; ``t`` is the reading that closed the previous phase (one
        read a boundary), None reads it. Inside, the driver times its
        own parts on the same clock (``phases["stage"]``, ``["launch"]``,
        ``["select"]``), and what the dispatch reads of the state, as
        the graph's ops count it (``drv.last_reads``), adds up in
        ``phases["reads"]``, a ``Counter``, the rows its program and
        its head ran over in ``phases["program_rows"]`` and
        ``["head_rows"]``, the copies of rows the program holds and
        those without a loop in ``["copy_sites"]`` and
        ``["static_copy_sites"]``. Returns what ``_fetch``
        takes - a ``_Launched``, all of it on the device, ``out`` the
        whole output where ``last`` is None (the speculative path: the
        verifier reads every row of target and draft) and None
        otherwise - and the reading that closed the phase."""
        now = self._clock.now
        if t is None:
            t = now()
        picked = ids = nxt = None
        with _telemetry.span("serve.decode.iter.dispatch"):
            if chip is not None:
                tokens = drv.merge_tokens(tokens, *chip)
            out = drv.step(tokens, fed=fed, now=now)
            drv.release_outputs()       # ``out`` is this call's alone
            phases["reads"].update(drv.last_reads)
            phases["stage"] += drv.last_stage
            phases["launch"] += drv.last_launch
            phases["program_rows"] += drv.last_program_rows
            phases["head_rows"] += drv.last_head_rows
            phases["copy_sites"] += drv.last_copy_sites[0]
            phases["static_copy_sites"] += drv.last_copy_sites[1]
            if last is not None:
                picked, ids, nxt = drv.select_rows(out, last, feed=feed,
                                                   now=now)
                out = None
                phases["select"] += drv.last_select
                if rows:
                    picked.copy_to_host_async()
            # where the tokens went, counted inside the program: the
            # copy queues behind it beside the ids', so reading it
            # afterwards waits for nothing further
            routed = drv.moe_stats_begin()
        t_launched = now()
        phases["dispatch"] += t_launched - t
        return _Launched(out, picked, ids, nxt, routed), t_launched

    def _fetch(self, drv, launched, phases, t, rows=False):
        """What the host samples from, ``serve.decode.iter.fetch``: waits
        for the device and copies the (rung,) int32 token ids of one
        ``_launch``, and the (rung, V) selected rows besides only where
        ``rows`` says a slot that samples in this iteration is not
        greedy; with no ids (the speculative path) the whole (rung, S,
        V) output. ``t`` is the reading that closed the phase before:
        the launches' own, or those of the dispatch launched behind
        this one. Adds the duration to ``phases["fetch"]`` and the
        bytes brought to the host to ``phases["bytes"]``;
        ``serve.decode.iter.fetch.ids`` is ``np.asarray(ids)`` alone
        (``phases["ids"]``), so that the rows and ``moe_stats``
        (``phases["reads"]``) are what is left of it. Returns ``(ids,
        logits, end)``: ``logits`` is the selected rows, the whole
        output, or None."""
        now = self._clock.now
        out, picked, ids, _nxt, routed = launched
        with _telemetry.span("serve.decode.iter.fetch"):
            if ids is None:
                logits = out.asnumpy()
                nbytes = logits.nbytes
            else:
                # the wait for the device, and 4 bytes a slot
                t_ids = now()
                with _telemetry.span("serve.decode.iter.fetch.ids"):
                    ids = np.asarray(ids)
                phases["ids"] += now() - t_ids
                logits = np.asarray(picked) if rows else None
                nbytes = ids.nbytes + (logits.nbytes if rows else 0)
            if routed is not None:
                with _telemetry.span("serve.decode.iter.moe_stats"):
                    phases["reads"].update(drv.moe_stats(routed))
                nbytes += routed.nbytes
        end = now()
        phases["fetch"] += end - t
        phases["bytes"] += nbytes
        return ids, logits, end

    def _step_fetch(self, drv, tokens, phases):
        """One dispatch whose whole output comes to the host, launched
        and fetched in one go (``_launch``, ``_fetch``): ``(None, logits,
        end)``."""
        launched, t = self._launch(drv, tokens, phases)
        return self._fetch(drv, launched, phases, t)

    def _dispatch_spec(self, drv, ddrv, base_tokens, meta, K, phases):
        """One speculative iteration's device work (runs OUTSIDE the
        scheduler lock, like every dispatch): K draft S=1 dispatches
        propose ``d_1..d_K`` per slot, then ONE target S=K window
        dispatch — window ``[t, d_1..d_{K-1}]`` at the slot's own
        cursor — yields the target distribution for every proposed
        position (row j verifies ``d_{j+1}``). Returns
        ``{row: (accepted, tokens)}`` from exact rejection sampling."""
        rung = base_tokens.shape[0]
        proposals = np.zeros((rung, K), np.int64)
        draft_rows = {row: [] for row, _seq in meta}
        feed = base_tokens.copy()
        for j in range(K):
            _, dlog, _ = self._step_fetch(ddrv, feed, phases)  # (rung, 1, V)
            feed = np.zeros((rung, 1), np.int32)
            for row, seq in meta:
                d = sample_token(dlog[row, 0], seq.sampling, seq.rng)
                proposals[row, j] = d
                draft_rows[row].append(dlog[row, 0])
                feed[row, 0] = d
        window = np.zeros((rung, K), np.int32)
        window[:, 0] = base_tokens[:, 0]
        if K > 1:
            window[:, 1:] = proposals[:, :K - 1]
        _, vlog, _ = self._step_fetch(drv, window, phases)  # (rung, K, V)
        out = {}
        for row, seq in meta:
            out[row] = speculative_verify(
                vlog[row], np.asarray(draft_rows[row]),
                proposals[row], seq.sampling, seq.rng)
        return out

    def _iterate(self):
        """One scheduling iteration, the commit of one dispatch;
        returns tokens emitted (0 = no work was ready).
        ``serve.decode.iter`` in the profiler's trace, enclosing its
        phases ``.plan``, ``.dispatch``, ``.fetch``, ``.commit``,
        ``.rewind`` and ``.account``; the ring record
        ``serve.decode.step`` carries the same ``iter`` number and the
        dispatch's durations on the scheduler's clock (``ahead`` 1:
        its plan and launches lie an iteration back)."""
        # read without the lock: only this, the iterating thread, ever
        # writes the count (pump() and the dispatch thread never overlap)
        it = self.iterations  # mxlint: guarded-by(gil)
        with _telemetry.span("serve.decode.iter", iter=it) as iter_span:
            return self._run_iteration(iter_span)

    def _retire_expired(self, now):
        """Complete the active sequences whose deadline has passed with
        their partial output (caller holds the lock)."""
        for seq in self._active():
            if seq.deadline is not None and now > seq.deadline:
                self._finish(seq, reason="deadline", now=now)

    def _plan_locked(self, now):
        """Retire, admit, pick the rung and plan one dispatch from the
        state as it stands (caller holds the lock): the ``_Dispatch``
        to launch, or None where nothing is active."""
        # retirement BEFORE dispatch: deadline-expired sequences
        # complete with their partial output; a slot whose next
        # token would overflow its cache slice fails ALONE — the
        # program was never dispatched for it, batchmates continue
        self._retire_expired(now)
        for row in self.engine.driver(self._rung).overflowing(
                self.engine.block_len or 1):
            seq = self._slots[row]
            if seq is None:          # retired row still advancing
                continue
            self._finish(seq, error=MXNetError(
                f"decode {self.engine.name!r}: sequence {seq.id} "
                f"overflowed its KV-cache slice (slot {row}, "
                f"capacity {self.engine.capacity}); shorten the "
                "prompt/max_new_tokens or re-bind with a larger "
                "capacity"), now=now)
        self._admit_locked(now)
        active = self._active()
        if not active:
            return None
        # shrink to the smallest rung covering the live set (frees
        # the larger pool's compute for the next iterations)
        target = self.engine.ladder.bucket_for(len(active))
        if target is not None and target < self._rung:
            self._switch_rung(target)
        if self._block is not None:
            return self._plan_block(now)
        cursors = self._cursors()[0]
        d = _Dispatch(*self._plan_dispatch(cursors), t0=now)
        if d.mode != "spec":
            self._fill_window(d, cursors, now)
            return d
        d.tokens = np.zeros((self._rung, 1), np.int32)
        for row, seq, at, _left in cursors:
            d.tokens[row, 0] = seq.stream_token(at)
            d.meta.append((row, seq))
            if seq.first_dispatch_at is None:
                seq.first_dispatch_at = now
        d.n_active = len(active)
        if any(s.trace is not None for s in active):
            d.shared_sid = _trace.next_span_id()
        return d

    def _plan_ahead(self, d, now):
        """The dispatch behind ``d``, of whatever length, planned while
        ``d`` is still on the chip (caller holds the lock), or None
        where the host cannot know it without ``d``'s ids. What
        ``_plan_locked`` plans after ``d``'s commit follows from every
        slot's cursor, prompt and ``max_new``, the queue, the deadlines
        and the rung, which the host has now; only the value of the id
        that a slot samples at ``d`` it has not, and ``d``'s select
        program leaves that on the chip. So this admits whom
        ``_admit_locked`` admits (a join behind ``d`` on the device)
        and plans with ``_plan_dispatch`` and ``_plan_window`` over the
        cursors as ``d``'s commit will leave them
        (``_cursors(after=d)``): a slot whose last token by length is
        ``d``'s is gone, a slot that still prefills takes the host's
        tokens, a slot that samples at ``d`` the chip's id as its one
        token (``_fill_window(chip=)``).

        None - plan after the commit - where ``_plan_locked`` would do
        what cannot be done behind ``d``'s back: a draft shadows the
        dispatch; a slot that samples at ``d`` is not greedy (its row
        has to be on the host first); somebody waits in the queue and a
        deadline there has passed (the caller has retired the active
        sequences whose has), a slot that ``d``'s commit frees would be
        theirs, a larger rung would, or one names a prefix that a store
        might join at a cursor; a smaller rung is due; a slot would
        overflow its
        cache. (A prefix captured
        at ``d``'s commit reads rows below the cursor, which a dispatch
        behind only appends to where the state is a row a position -
        all a prefix store is built over, ``__init__`` refuses any
        other: ``docs/serving.md``.) What
        the host cannot know is an EOS, or whom a caller submits from
        ``d``'s callbacks: a slot that retires at ``d``'s commit has had
        one token computed for it, which the commit of this dispatch
        drops, and a request that arrives while this dispatch is on its
        way is admitted by the plan behind it. ``d`` need not be
        launched yet: the ids are ``d``'s to give once it is.

        Over an engine that decodes by blocks the same, a block
        dispatch behind a block dispatch (``_plan_block_ahead``)."""
        if d.mode != "window" or self.draft is not None:
            return None
        if self._block is not None:
            return self._plan_block_ahead(d, now)
        cursors, sampled, leaving = self._cursors(after=d)
        if any(at + 1 > self.engine.capacity
               for _row, _seq, at, _left in cursors) or \
                not all(self._slots[row].sampling.greedy for row in sampled):
            return None
        if self._queue:
            free = None in self._slots
            if not self._admit_behind(leaving, now):
                return None
            if free:            # somebody joined
                cursors, sampled, leaving = self._cursors(after=d)
        if not cursors or \
                self.engine.ladder.bucket_for(len(cursors)) != self._rung:
            return None         # nobody left, or a smaller rung is due
        mode, S = self._plan_dispatch(cursors)
        # until ``d``'s commit whoever leaves there is the driver's to
        # guard: it refuses a step that their cursor has no room for
        if any(at + S > self.engine.capacity for at in leaving):
            return None
        nxt = _Dispatch(mode, S, t0=None, ahead=True)
        self._fill_window(nxt, cursors, now, chip=sampled)
        if S == 1 and len(sampled) == len(nxt.meta):
            nxt.tokens = None   # ``d``'s ids as they lie on the chip
        elif sampled:
            nxt.chip = np.zeros(self._rung, bool)
            nxt.chip[sorted(sampled)] = True
        return nxt

    def _admit_behind(self, leaving, now):
        """Admit whoever waits in the queue into the slots that are
        free now, behind a dispatch on the chip (caller holds the lock;
        the queue is not empty). False, and nobody admitted, where that
        waits for the dispatch's commit: a slot frees there
        (``leaving``) and would be theirs, a larger rung would, a
        deadline in the queue has passed, or one names a prefix that a
        store might join at a cursor."""
        want = min(len(self._active()) + len(self._queue),
                   self.engine.ladder.max)
        if leaving or self.engine.ladder.bucket_for(want) > self._rung \
                or any(s.deadline is not None and now > s.deadline
                       or s.prefix_id is not None
                       and self.prefix_store is not None
                       for s in self._queue):
            return False
        if None in self._slots:
            self._admit_locked(now)
        return True

    def _run_iteration(self, iter_span):
        """Commit one dispatch: the one launched an iteration ago
        (``self._ahead``), or one planned and launched here. Between
        its launch and the fetch of its ids the dispatch behind it is
        launched too where the host can plan it without them
        (``_plan_ahead``): the chip then goes from one program to the
        next, window or S=1 step, while the host fetches, commits and
        plans."""
        span = _telemetry.span
        clock = self._clock.now
        # a submit (a closed-loop caller's done callback) holds the lock
        # this waits for: read the clock on both sides of it
        arrived = clock()
        with span("serve.decode.iter.plan"), self._lock:
            locked = clock()
            turn_from, self._turn_from = self._turn_from, None
            d, self._ahead = self._ahead, None
            fresh = d is None
            if fresh:
                d = self._plan_locked(locked)
                if d is None:
                    self._gauge("active").set(0)
                    self._gauge("occupancy").set(0.0)
                    return 0
            else:
                # launched an iteration ago: a sequence whose time ran
                # out since leaves here, as before any plan, and the
                # token on its way is dropped at the commit
                self._retire_expired(locked)
            nxt = self._plan_ahead(d, locked)
            drv = self.engine.driver(self._rung)
            ddrv = self.draft.driver(self._rung) if self.draft else None
            iter_span.set(mode=d.mode, window=d.S, rung=self._rung,
                          active=d.n_active)
            planned = clock()
            # the section's time is the plan of what it planned first
            # (of the dispatch it commits where it planned nothing)
            (d if fresh else nxt or d).plan_s += planned - locked

        # dispatch outside the lock: submits stay non-blocking while
        # the program runs (only pump()/the dispatch thread iterates,
        # so the engine itself needs no second guard)
        if d.mode == "spec":
            verdicts = self._dispatch_spec(
                drv, ddrv, d.tokens, d.meta, d.S, d.phases)
            end = clock()
        elif d.block:
            if fresh:
                d.launched, planned = self._launch_block(drv, d, planned)
            if nxt is not None:
                nxt.launched, planned = self._launch_block(
                    drv, nxt, planned, state=d.launched[0])
            ids, end = self._fetch_block(drv, d, planned)
            if nxt is not None:
                nxt.t0 = end    # a feed a slot from here to its own ids
        else:
            if fresh:
                d.launched, planned = self._launch(
                    drv, d.tokens, d.phases, t=planned, last=d.last,
                    rows=d.want_rows, fed=d.fed, feed=d.feed)
            if nxt is not None:
                ids = d.launched.tokens
                nxt.launched, planned = self._launch(
                    drv, ids if nxt.tokens is None else nxt.tokens,
                    nxt.phases, t=planned, last=nxt.last,
                    rows=nxt.want_rows, fed=nxt.fed, feed=nxt.feed,
                    chip=None if nxt.chip is None else (ids, nxt.chip))
            ids, picked, end = self._fetch(drv, d.launched, d.phases,
                                           planned, rows=d.want_rows)
            if nxt is not None:
                nxt.t0 = end    # a token a slot from here to its own ids
            if ddrv is not None:
                # the draft shadows every non-speculative dispatch so
                # its cache tracks the same stream positions; nobody
                # reads its logits, so it is launched and not waited for
                with span("serve.decode.iter.dispatch"):
                    ddrv.step(d.tokens, fed=d.fed, now=clock)
                d.phases["stage"] += ddrv.last_stage
                d.phases["launch"] += ddrv.last_launch
                launched = clock()
                d.phases["dispatch"] += launched - end
                end = launched

        mode, S, phases = d.mode, d.S, d.phases
        with self._lock:
            step_s = max(0.0, end - d.t0)
            self.engine.note_exec(self._rung if S == 1
                                  else (self._rung, S), step_s)
            chunks = 0
            rew_rows, rew_pos = [], []
            with span("serve.decode.iter.commit"):
                if mode == "spec":
                    emitted = self._commit_spec(
                        d.meta, verdicts, S, d.t0, end, d.shared_sid,
                        rew_rows, rew_pos)
                elif d.block:
                    emitted, chunks = self._commit_block(d, ids, end)
                    if nxt is not None:
                        self._settle_block(nxt, ids)
                else:
                    emitted, chunks = self._commit_window(
                        d, ids, picked, end)
            committed = clock()
            with span("serve.decode.iter.rewind"):
                # retired rows keep advancing one window per dispatch;
                # pull any nearing capacity back to 0, so that a row
                # nobody owns stays inside its pool (past the capacity
                # its write is dropped, its cursor would grow for ever)
                maxw = max([1] + list(drv.window_lens))
                seen = set(rew_rows)
                for row in range(self._rung):
                    if self._slots[row] is None and row not in seen and \
                            drv.pos[row] + maxw > self.engine.capacity:
                        rew_rows.append(row)
                        rew_pos.append(0)
                if rew_rows:
                    drv.rewind_many(rew_rows, rew_pos)
                    if ddrv is not None:
                        ddrv.rewind_many(rew_rows, rew_pos)
            rewound = clock()
            # the next iteration's ``turn_us`` runs from here: the
            # account below, the loop, and up to its read before the lock
            self._turn_from = rewound
            self._ahead = nxt
            with span("serve.decode.iter.account"):
                it = self.iterations
                self.iterations += 1
                n_active = len(self._active())
                m = self._iter_metrics()
                m["iterations"].inc()
                if emitted:
                    m["tokens"].inc(emitted)
                if chunks:
                    m["prefill.chunks"].inc(chunks)
                if nxt is not None:
                    m["runahead.launched"].inc()
                m["fetch.bytes"].inc(phases["bytes"])
                if S > 1 and mode != "spec":
                    m["window.dispatches"].inc()
                    if d.ahead:
                        m["runahead.windows"].inc()
                        if d.block:
                            m["runahead.blocks"].inc()
                    rows = [n for _row, _seq, n in d.meta]
                    m["window.fed_slots"].inc(sum(n >= 1 for n in rows))
                    m["window.riding_slots"].inc(sum(n == 1 for n in rows))
                    m["window.real_rows"].inc(sum(rows))
                    m["window.program_rows"].inc(phases["program_rows"])
                    m["window.head_rows"].inc(phases["head_rows"])
                    m["window.copy_sites"].inc(phases["copy_sites"])
                    m["window.static_copy_sites"].inc(
                        phases["static_copy_sites"])
                # what the dispatches read of the state, under the
                # names its ops gave: a counter, a ring field, or both
                read_fields = {}
                for key, value in phases["reads"].items():
                    counter, field = drv.read_counts[key]
                    if counter:
                        m[counter].inc(value)
                    if field:
                        read_fields[field] = value
                m["step.seconds"].observe(step_s)
                m["active"].set(n_active)
                m["occupancy"].set(n_active / self._rung)
                m["queue.depth"].set(len(self._queue))
                compiles = self.engine.compiles_since_warmup()
                m["compiles_since_warmup"].set(compiles or 0)

                _telemetry.flightrec.note(
                    "serve.decode.step", model=self.engine.name, iter=it,
                    rung=self._rung, active=n_active,
                    step_us=_us(step_s), plan_us=_us(d.plan_s),
                    dispatch_us=_us(phases["dispatch"]),
                    stage_us=_us(phases["stage"]),
                    launch_us=_us(phases["launch"]),
                    select_us=_us(phases["select"]),
                    fetch_us=_us(phases["fetch"]),
                    ids_us=_us(phases["ids"]),
                    commit_us=_us(committed - end),
                    rewind_us=_us(rewound - committed),
                    turn_us=0 if turn_from is None
                    else _us(arrived - turn_from),
                    lock_us=_us(locked - arrived), mode=mode, window=S,
                    ahead=int(d.ahead),
                    compiles_since_warmup=compiles, **read_fields,
                    **({"block": d.block,
                        "tentative": len(d.back[0]),
                        "decided": phases["decided"],
                        "denoise_us": _us(phases["denoise"])}
                       if d.block else {}))
        return max(1, emitted)

    def _commit_window(self, d, ids, picked, end):
        """Apply the outcome of one window (or S=1) dispatch ``d``
        (caller holds the lock): ``ids[row]`` is the argmax of the
        slot's last fed row, taken on the device, and ``picked[row]``
        that row itself, fetched only when a slot sampling now is not
        greedy. Where a slot's stream is exhausted a greedy request
        takes its id and any other hands its row to ``sample_token``;
        stream the tokens and retire on EOS / max-new (the program
        advanced each slot by what it was fed: nobody is rewound). A
        slot that retired while ``d``, launched ahead, was on the chip
        has its token dropped and counted
        (``serve.decode.runahead.dropped``). Returns ``(emitted,
        prefill chunks)``."""
        S, t0, shared_sid, n_active = d.S, d.t0, d.shared_sid, d.n_active
        dropped = 0
        emitted = chunks = 0
        on_device = on_host = 0
        for row, seq, n in d.meta:
            if seq.slot is None:
                dropped += d.ahead
                continue
            was_prefilling = seq.remaining() > 1
            # (a window of a block engine carries prompt tokens alone,
            # and a block's tokens are its own feeds' to decide)
            samples = seq.fed + n == seq.stream_len() \
                and self._block is None
            if seq.trace is not None:
                _trace.record(
                    seq.trace, "serve.decode.step", t0, end,
                    span_id=shared_sid, parent=seq.root_sid,
                    rung=self._rung, n_active=n_active,
                    shared=True, pos=seq.fed, window=n)
                if was_prefilling:
                    _trace.record(
                        seq.trace, "serve.decode.prefill",
                        t0, end, parent=seq.root_sid,
                        pos=seq.fed, tokens=n, chunk=S)
            if was_prefilling:
                chunks += 1
            tok = None
            if samples and seq.sampling.greedy:
                tok = int(ids[row])
                on_device += 1
            elif samples:
                tok = sample_token(picked[row], seq.sampling, seq.rng)
                on_host += 1
            seq.fed += n
            self._capture_prefix(seq, end)
            if not samples:
                continue              # still prefilling
            if seq.eos_id is not None and tok == seq.eos_id:
                self._finish(seq, reason="eos", now=end)
                continue            # EOS retires, not emitted
            seq.generated.append(tok)
            seq.handle._emit(tok, now=end)
            emitted += 1
            if len(seq.generated) >= seq.max_new:
                self._finish(seq, reason="length", now=end)
        m = self._iter_metrics()
        m["sample.device"].inc(on_device)
        m["sample.host"].inc(on_host)
        if dropped:
            m["runahead.dropped"].inc(dropped)
        return emitted, chunks

    # ------------------------------------------------- decoding by blocks
    def _block_cursors(self, after=None):
        """``(row, seq, at, blk, chip)`` of every live slot of an engine
        that decodes by blocks (caller holds the lock): its cursor, its
        block in flight (None: its next feed prefills, or starts one)
        and whether that block's ids are the chip's to give - as they
        stand, or as the commit of the block dispatch ``after`` will
        leave them. The host has committed the dispatch before
        ``after``, so it knows what ``after`` is to each slot
        (``after.kinds``): a feed that is kept moves the cursor by
        ``L`` and closes the block, and the request with it once its
        stream stopped or reached its length (``_commit_block``); a
        tentative feed leaves the cursor, and the block's ids, its mask
        and whether the next feed keeps its rows on the chip. A slot
        joined behind ``after`` stands as it is. Beside them the
        cursors of those who leave at ``after``'s commit."""
        L = self.engine.block_len
        fed_by = {} if after is None else {
            row: seq for row, seq, _n in after.meta}
        cursors, leaving = [], []
        for row, seq in enumerate(self._slots):
            if seq is None:
                continue
            kind = after.kinds[row] if fed_by.get(row) is seq else None
            if kind in ("prefill", "commit"):
                at = seq.fed + L
                if kind == "commit" and (
                        seq.stopped is not None
                        or at >= len(seq.prompt) + seq.max_new):
                    leaving.append(at)
                    continue
                cursors.append((row, seq, at, None, False))
            else:
                cursors.append((row, seq, seq.fed, seq.block,
                                kind == "tentative"))
        return cursors, leaving

    def _plan_block(self, now, after=None, cursors=None):
        """One dispatch of an engine that decodes by blocks, from the
        state as it stands (caller holds the lock). While a slot with
        room for a chunk still prefills, a window: the prefilling slots
        take ``min(chunk, what is left of their prompt's whole blocks,
        what is left of the packed budget)`` rounded down to whole
        blocks, oldest admission first, and **the decoding slots wait
        that iteration** (fed 0; a window needs room for a chunk behind
        every live cursor, as ``_plan_dispatch``'s). Otherwise a
        dispatch of one block a slot: a decoding slot its block in
        flight (a new one starts as the prompt's last ``P mod L``
        tokens and the mask id elsewhere) - fed to be taken back while
        a position is undecided, once more to be kept when none is -
        and a slot that prefills without room for a chunk its next
        ``L`` prompt tokens, kept.

        With ``after``, a block dispatch still on the chip, the block
        dispatch behind it from the state as ``after``'s commit will
        leave it (``_block_cursors(after=)``, or ``cursors`` where the
        caller has them; ``_plan_block_ahead`` says when), or None
        where that is a window, nobody is left, a smaller rung is due
        or a cache has no room for ``L`` more rows. A slot whose feed
        at ``after`` is tentative is fed what that feed decides -
        ``"chip"``: the host's row of tokens and mask stays 0 and
        ``_launch_block`` merges the chip's in; the quota is its next
        feed's, the cursor among ``back`` in case -, and a block that
        starts here is held in ``blocks`` until ``after``'s commit has
        closed the one before (``_settle_block``)."""
        L, S, cap = self.engine.block_len, self.prefill_chunk, \
            self.engine.capacity
        live, leaving = cursors or self._block_cursors(after)
        # until ``after``'s commit whoever leaves there is the driver's
        # to guard: it refuses a step that their cursor has no room for
        if after is not None and (
                not live or self.engine.ladder.bucket_for(len(live))
                != self._rung or any(
                    at + L > cap for at in leaving
                    + [at for _row, _seq, at, *_b in live])):
            return None
        left = {row: max(0, len(seq.prompt) // L * L - at)
                for row, seq, at, _blk, _chip in live}
        # a window writes S rows behind every live cursor: all have room
        # for them, or the slots that prefill go a block at a time
        chunked = [(row, seq) for row, seq, *_at in live if left[row]] \
            if S > L and all(at + S <= cap for _row, _seq, at, *_b in live) \
            else []
        if chunked and after is not None:
            return None
        d = _Dispatch("window", S if chunked else L,
                      t0=now if after is None else None,
                      ahead=after is not None)
        d.tokens = np.zeros((self._rung, d.S), np.int32)
        d.fed = np.zeros(self._rung, np.int32)
        d.n_active = len(live)
        if any(seq.trace is not None for _row, seq, *_at in live):
            d.shared_sid = _trace.next_span_id()
        if chunked:
            d.last = np.zeros(self._rung, np.int32)
            d.feed = np.zeros(self._rung, bool)
            room = self.engine.window_budget(self._rung, S)
            room = S * len(chunked) if room is None else room
            for row, seq in sorted(chunked, key=lambda c: c[1].id):
                n = min(S, left[row], room) // L * L
                if not n:
                    continue
                room -= n
                d.tokens[row, :n] = seq.prompt[seq.fed:seq.fed + n]
                d.last[row], d.fed[row] = n - 1, n
                d.meta.append((row, seq, n))
            d.meta.sort(key=lambda entry: entry[0])
        else:
            d.block = L
            d.undecided = np.zeros((self._rung, L), bool)
            d.quota = np.zeros(self._rung, np.int32)
            d.threshold = np.full(self._rung, np.inf, np.float32)
            for row, seq, at, blk, chip in live:
                d.fed[row] = L
                d.meta.append((row, seq, L))
                if left[row]:
                    d.tokens[row] = seq.prompt[at:at + L]
                    d.kinds[row] = "prefill"
                    continue
                if chip:
                    if d.chip is None:
                        d.chip = np.zeros(self._rung, bool)
                    d.chip[row] = True
                    d.kinds[row] = "chip"
                    feeds = blk.feeds + 1
                else:
                    if blk is None:
                        blk = d.blocks[row] = _Block(
                            seq, self._block["mask_token_id"], at)
                    d.tokens[row] = blk.ids
                    if not blk.undecided.any():
                        d.kinds[row] = "commit"
                        continue
                    d.kinds[row] = "tentative"
                    d.undecided[row] = blk.undecided
                    feeds = blk.feeds
                steps, d.threshold[row] = self._denoising(seq)
                # L / steps a feed, the remainder to the first feeds
                d.quota[row] = L // steps + (feeds < L % steps) \
                    if feeds < steps else L
                d.back[0].append(row)
                d.back[1].append(at)
        if after is None:
            self._settle_block(d)
        for _row, seq, _n in d.meta:
            if seq.first_dispatch_at is None:
                seq.first_dispatch_at = now
        return d

    def _plan_block_ahead(self, d, now):
        """``_plan_ahead`` of an engine that decodes by blocks: the
        block dispatch behind the block dispatch ``d``, planned while
        ``d`` is on the chip (caller holds the lock), or None. What a
        slot's next feed needs of a tentative feed at ``d`` -
        the ids with the decided positions filled in, the mask of what
        is still undecided, and with that whether the next feed is the
        one that is kept - ``denoise_select`` leaves on the chip;
        everything else (``_block_cursors(after=d)``) the host has.

        None under ``_plan_ahead``'s own conditions - somebody waits in
        the queue and a deadline there has passed, a slot that ``d``'s
        commit frees would be theirs, a larger rung would, or one names
        a prefix to a store; a smaller rung is due; a cache has no room
        for ``L`` more rows - and where ``d`` or the dispatch behind it
        is a window of prefill chunks, which is planned after the
        commit. A request's last block is known by its length, and an
        ``eos_id`` inside a block by the feed before the one that keeps
        it (``seq.stopped``), so a slot that leaves at ``d`` is planned
        as gone; whom a caller submits from ``d``'s callbacks is
        admitted by the plan behind, and a sequence whose deadline
        passes while its feed is on the chip has that feed dropped
        (``serve.decode.runahead.dropped``)."""
        if not d.block:
            return None
        cursors = self._block_cursors(after=d)
        if self._queue:
            free = None in self._slots
            if not self._admit_behind(cursors[1], now):
                return None
            if free:            # somebody joined
                cursors = self._block_cursors(after=d)
        return self._plan_block(now, after=d, cursors=cursors)

    def _settle_block(self, d, state=None):
        """What the commit of the dispatch before tells the block
        dispatch ``d`` (caller holds the lock; of a dispatch planned
        after that commit, at once): a block that starts at ``d`` is
        its sequence's from here, and of a slot whose ids were the
        chip's (``state``: the ``(2, rung, L)`` ids and mask of the
        dispatch before, now on the host) ``d`` is the feed that is
        kept where nothing was left undecided, and a tentative one
        otherwise - as the device has decided already
        (``rewind_many(where=)``), and the driver is told who stayed
        (``kept``). ``back`` is from here the slots whose feed keeps
        nothing."""
        for row, seq, _n in d.meta:
            if row in d.blocks and seq.slot is not None:
                seq.block = d.blocks[row]
        if d.chip is None:
            return
        rows, cursors, stayed = [], [], []
        for row, at in zip(*d.back):
            if d.kinds[row] == "chip":
                d.kinds[row] = "tentative" if state[1][row].any() \
                    else "commit"
            if d.kinds[row] == "commit":
                stayed.append(row)
                continue
            rows.append(row)
            cursors.append(at)
        self.engine.driver(self._rung).kept(stayed)
        d.back = (rows, cursors)

    def _launch_block(self, drv, d, t, state=None):
        """The launches of a dispatch of one block a slot,
        ``serve.decode.iter.dispatch``: the step, ``denoise_select``
        behind it over all its rows (``decode.denoise_select``), the
        cursors of the slots whose feed keeps nothing put back
        (``rewind_many``: one small launch, queued behind the step, so
        the host waits for neither) and the ``moe_stats`` stack.
        Where some slots' blocks are the chip's (``d.chip``; ``state``
        is what the dispatch before decided, as ``denoise_select`` left
        it there) one launch more in front, ``merge_block``: the step
        and the decision take its ids and mask as they lie, and the
        cursors go back where its third result says a block has a
        position undecided.
        Returns ``((state, routed), the reading that closed the
        phase)``; ``phases["denoise"]`` runs from the decision
        program's launch to its ids on the host (``_fetch_block``)."""
        now, phases = self._clock.now, d.phases
        with _telemetry.span("serve.decode.iter.dispatch"):
            tokens, undecided, back = d.tokens, d.undecided, None
            if d.chip is not None:
                tokens, undecided, back = drv.merge_block(
                    tokens, undecided, state, d.chip)
            out = drv.step(tokens, fed=d.fed, now=now)
            drv.release_outputs()       # ``out`` is this call's alone
            phases["reads"].update(drv.last_reads)
            phases["stage"] += drv.last_stage
            phases["launch"] += drv.last_launch
            phases["program_rows"] += drv.last_program_rows
            phases["head_rows"] += drv.last_head_rows
            phases["copy_sites"] += drv.last_copy_sites[0]
            phases["static_copy_sites"] += drv.last_copy_sites[1]
            phases["denoise"] = -now()
            state = drv.denoise_select(out, tokens, undecided, d.quota,
                                       d.threshold, now=now)
            phases["select"] += drv.last_select
            out = None
            if d.back[0]:
                drv.rewind_many(*d.back, where=back)
            routed = drv.moe_stats_begin()
        t_launched = now()
        phases["dispatch"] += t_launched - t
        return (state, routed), t_launched

    def _fetch_block(self, drv, d, t):
        """What a dispatch of one block a slot decided, on the host:
        ``(2, rung, L)`` int32 - the ids and which positions are still
        undecided -, 8 x L bytes a slot and never a row of logits.
        ``serve.decode.iter.fetch`` as ``_fetch``."""
        now, phases = self._clock.now, d.phases
        state, routed = d.launched
        with _telemetry.span("serve.decode.iter.fetch"):
            t_ids = now()
            with _telemetry.span("serve.decode.iter.fetch.ids"):
                state = np.asarray(state)
            t_got = now()
            phases["ids"] += t_got - t_ids
            phases["denoise"] += t_got
            nbytes = state.nbytes
            if routed is not None:
                with _telemetry.span("serve.decode.iter.moe_stats"):
                    phases["reads"].update(drv.moe_stats(routed))
                nbytes += routed.nbytes
        end = now()
        phases["fetch"] += end - t
        phases["bytes"] += nbytes
        return state, end

    def _commit_block(self, d, state, end):
        """Apply a dispatch of one block a slot (caller holds the
        lock). A tentative feed: the block takes the ids and the mask
        the device decided (``serve.decode.iter.denoise``), and every
        token with no undecided position before it is streamed, in
        stream order, up to ``max_new`` exactly - a decided position is
        final; an ``eos_id`` stops the stream there. A feed that was
        kept moves the cursor by ``L``; where it was a block's, the
        block is done, and the request with it once its stream stopped
        or reached its length (positions of that last block past the
        length were denoised and are nobody's). A slot that retired
        while ``d``, launched ahead, was on the chip has its feed
        dropped and counted (``serve.decode.runahead.dropped``).
        Returns ``(emitted, prefill chunks)``."""
        L = d.block
        emitted = chunks = feeds = blocks = decided = undelivered = 0
        dropped = 0
        ids, left = state
        for row, seq, _n in d.meta:
            if seq.slot is None:
                dropped += d.ahead
                continue
            kind = d.kinds[row]
            if seq.trace is not None:
                _trace.record(
                    seq.trace, "serve.decode.step", d.t0, end,
                    span_id=d.shared_sid, parent=seq.root_sid,
                    rung=self._rung, n_active=d.n_active, shared=True,
                    pos=seq.fed, window=L, feed=kind)
            if kind == "prefill":
                chunks += 1
                seq.fed += L
                self._capture_prefix(seq, end)
                continue
            feeds += 1
            blk = seq.block
            if kind == "tentative":
                with _telemetry.span("serve.decode.iter.denoise"):
                    still = left[row].astype(bool)
                    decided += int(blk.undecided.sum() - still.sum())
                    blk.ids, blk.undecided = ids[row].copy(), still
                    blk.feeds += 1
                    emitted += self._deliver(seq, blk, end)
                continue
            blocks += 1
            seq.fed += L
            seq.block = None
            self._capture_prefix(seq, end)
            over = seq.fed - len(seq.prompt) - seq.max_new
            if seq.stopped is not None:
                self._finish(seq, reason=seq.stopped, now=end)
            elif over >= 0:
                undelivered += over
                self._finish(seq, reason="length", now=end)
        d.phases["decided"] = decided
        m = self._iter_metrics()
        m["sample.device"].inc(decided)
        if dropped:
            m["runahead.dropped"].inc(dropped)
        for key, n in (("feeds", feeds), ("blocks", blocks),
                       ("decided", decided),
                       ("rows_dropped", L * len(d.back[0])),
                       ("undelivered", undelivered)):
            if n:
                m[f"diffusion.{key}"].inc(n)
        return emitted, chunks

    def _deliver(self, seq, blk, now):
        """Stream the tokens of ``seq``'s block that no undecided
        position precedes and nobody has been handed yet; returns
        their count."""
        emitted = 0
        while seq.stopped is None and len(seq.generated) < seq.max_new:
            j = len(seq.prompt) + len(seq.generated) - seq.fed
            if j >= seq.block_len or blk.undecided[:j + 1].any():
                break
            tok = int(blk.ids[j])
            if seq.eos_id is not None and tok == seq.eos_id:
                seq.stopped = "eos"     # retires at the block's commit
                break
            seq.generated.append(tok)
            seq.handle._emit(tok, now=now)
            emitted += 1
        return emitted

    def _commit_spec(self, meta, verdicts, K, t0, end, shared_sid,
                     rew_rows, rew_pos):
        """Apply one speculative iteration's verdicts (caller holds the
        lock): commit each slot's accepted prefix + rejection sample,
        stream the tokens, roll the cursor back over the rejected tail
        (both engines, via the caller's rewind batch), retire on EOS /
        max-new mid-window (tokens past the stop are discarded — the
        target never sampled them)."""
        emitted = 0
        for row, seq in meta:
            if seq.slot is None:
                continue
            accepted, toks = verdicts[row]
            self.spec_proposed += K
            self.spec_accepted += accepted
            if accepted < K:
                self.spec_rollbacks += 1
            committed = 0
            finish = None
            for tok in toks:
                if seq.eos_id is not None and tok == seq.eos_id:
                    finish = "eos"
                    break
                seq.generated.append(int(tok))
                seq.handle._emit(int(tok), now=end)
                emitted += 1
                committed += 1
                if len(seq.generated) >= seq.max_new:
                    finish = "length"
                    break
            seq.fed += committed
            if committed < K:
                rew_rows.append(row)
                rew_pos.append(seq.fed)
            if seq.trace is not None:
                _trace.record(
                    seq.trace, "serve.decode.step", t0, end,
                    span_id=shared_sid, parent=seq.root_sid,
                    rung=self._rung, shared=True, pos=seq.fed,
                    spec_k=K, accepted=accepted, committed=committed)
            if finish is not None:
                self._finish(seq, reason=finish, now=end)
        # the verifier drew these on the host, from the fetched logits
        self._iter_metrics()["sample.host"].inc(
            sum(len(verdicts[r][1]) for r, _ in meta))
        self._counter("spec.proposed").inc(K * len(meta))
        accepted_now = sum(verdicts[r][0] for r, _ in meta)
        if accepted_now:
            self._counter("spec.accepted").inc(accepted_now)
        return emitted

    def _capture_prefix(self, seq, now):
        """Snapshot a cold prefix the moment its prefill completes
        (caller holds the lock): the slot's first ``len(prompt)`` cache
        positions on the target (and draft, when armed) plus the token
        ids they encode."""
        # of a model that decodes by blocks the prompt's whole blocks
        # alone: the rows of its last P mod L tokens are their block's,
        # and depend on what was generated beside them
        length = len(seq.prompt) if self._block is None \
            else len(seq.prompt) // seq.block_len * seq.block_len
        if not seq.prefix_cold or self.prefix_store is None or \
                seq.slot is None or seq.fed < length:
            return
        payloads = {"target": self.engine.driver(self._rung)
                    .capture_rows(seq.slot, length)}
        if self.draft is not None:
            payloads["draft"] = self.draft.driver(self._rung) \
                .capture_rows(seq.slot, length)
        stored = self.prefix_store.put(
            seq.prefix_id, np.asarray(seq.prompt[:length], np.int64),
            payloads)
        seq.prefix_cold = False
        if stored:
            self._counter("prefix.captures").inc()

    # ----------------------------------------------------------- drive modes
    def _has_work(self):
        return bool(self._queue) or self._ahead is not None or any(
            s is not None for s in self._slots)

    def pump(self, max_iterations=None):
        """Deterministic drive: run scheduler iterations until nothing
        is active or queued (or ``max_iterations``). The FakeClock
        path — no thread, no sleeps. Returns iterations run."""
        done = 0
        while max_iterations is None or done < max_iterations:
            with self._lock:
                if not self._has_work():
                    break
            emitted = self._iterate()
            with self._lock:
                if emitted == 0 and not self._queue:
                    break
            done += 1
        return done

    def _loop(self):
        while True:
            with self._cond:
                if not self._running:
                    return
                if not self._has_work():
                    # bounded wait so queued-request deadlines are
                    # noticed; a submit notifies sooner
                    with _telemetry.span("serve.decode.idle"):
                        self._cond.wait(timeout=0.05)
                    self._turn_from = None    # a wait is nobody's turn
                    continue
            self._iterate()

    def start(self):
        """Spawn the decode dispatch thread (idempotent)."""
        with self._cond:
            if self._running:
                return self
            self._running = True
        self._thread = threading.Thread(target=self._loop,
                                        name="mxnet-serve-decode",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, drain=True):
        """Stop the thread; ``drain`` finishes in-flight and queued
        sequences first, else they fail with MXNetError."""
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=60)
            self._thread = None
        if drain:
            self.pump()
        else:
            with self._lock:
                now = self._clock.now()
                for seq in list(self._active()) + self._queue:
                    self._finish(seq, error=MXNetError(
                        "decode scheduler stopped"), now=now)
                self._queue = []
                self._ahead = None      # nobody is left to take its ids

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # ---------------------------------------------------------------- stats
    def stats(self):
        """Snapshot for dashboards/bench: slot occupancy, queue depth,
        token/iteration counters, per-rung step estimates, and the
        zero-compile gate reading."""

        def c(key):
            m = _telemetry.get_metric(f"serve.decode.{key}",
                                      model=self.engine.name)
            return m.value if m is not None else 0

        with self._lock:
            n_active = len(self._active())
            depth = len(self._queue)
            rung = self._rung
            spec_proposed = self.spec_proposed
            spec_accepted = self.spec_accepted
            spec_rollbacks = self.spec_rollbacks
        h = _telemetry.get_metric("serve.decode.request.latency.seconds",
                                  model=self.engine.name)
        its = c("iterations")
        # exec_est keys mix rungs (int) and (rung, window) tuples —
        # render both as strings ("8", "8xS64") for a stable sort
        exec_est = {
            (f"{k[0]}xS{k[1]}" if isinstance(k, tuple) else str(k)):
            round(s * 1e3, 3) for k, s in self.engine.exec_est.items()}
        out = {
            "model": self.engine.name,
            "ladder": self.engine.ladder.sizes,
            "rung": rung,
            "active": n_active,
            "occupancy": round(n_active / rung, 4) if rung else None,
            "queue_depth": depth,
            "requests": c("requests"),
            "responses": c("responses"),
            "errors": c("errors"),
            "iterations": its,
            "tokens": c("tokens"),
            "tokens_per_iteration": round(c("tokens") / its, 3)
            if its else None,
            "joins": c("joins"),
            "leaves": c("leaves"),
            "migrations": c("migrations"),
            "prefill_chunk": self.prefill_chunk,
            "prefill_chunks": c("prefill.chunks"),
            "runahead": {"launched": c("runahead.launched"),
                         "windows": c("runahead.windows"),
                         "blocks": c("runahead.blocks"),
                         "dropped": c("runahead.dropped")},
            "latency_ms": None if h is None or not h.count else {
                "p50": round((h.quantile(0.50) or 0) * 1e3, 3),
                "p99": round((h.quantile(0.99) or 0) * 1e3, 3),
                "mean": round(h.mean * 1e3, 3)},
            "exec_est_ms": dict(sorted(exec_est.items())),
            "capacity": self.engine.capacity,
            "params_bytes": dict(self.engine.params_bytes),
            "state_bytes": self.engine.state_bytes,
            "params_narrowed": self.engine.params_narrowed,
            "compiles_since_warmup": self.engine.compiles_since_warmup(),
            "backend_compiles_since_warmup":
            self.engine.backend_compiles_since_warmup(),
            "launch_work_since_warmup":
            self.engine.launch_work_since_warmup(),
            "programs_resident": self.engine.programs_resident(),
        }
        if self.spec_k:
            out["spec"] = {
                "k": self.spec_k,
                "proposed": spec_proposed,
                "accepted": spec_accepted,
                "rollbacks": spec_rollbacks,
                "acceptance": round(spec_accepted / spec_proposed, 4)
                if spec_proposed else None,
            }
        if self.prefix_store is not None:
            out["prefix"] = self.prefix_store.stats()
        if self._block is not None:
            out["diffusion"] = dict(
                self._block, **{k.split(".")[1]: c(k)
                                for k in _DIFFUSION_COUNTERS})
        return out


def serve_decoder(symbol, arg_params, name="decoder", capacity=None,
                  ladder=None, clock=None, start=True, max_queue=None,
                  default_max_new=None, context=None, compute_dtype=None,
                  logger=None, symbol_gen=None, prefill_chunk=None,
                  draft_symbol_gen=None, draft_params=None, spec_k=None,
                  prefix_cache_mb=None):
    """One-call front end for continuous decode batching:
    ``serve_decoder(decode_symbol, params).submit([ids...])``.

    ``symbol`` is a per-slot decode graph
    (``get_decode_symbol(per_slot=True)``); builds the slot-rung
    ``DecodeEngine``, warms+pins every rung, and (by default) starts
    the dispatch thread — ``start=False`` + ``pump()`` with a FakeClock
    is the deterministic test path, mirroring ``serve()``.

    Fast paths (each optional, all off by default):

    * ``symbol_gen`` — ``symbol_gen(step_len) -> Symbol`` for the SAME
      model; arms chunked prefill (window S =
      ``prefill_chunk``/``MXNET_SERVE_PREFILL_CHUNK``) so a T-token
      prompt lands in ⌈T/S⌉ dispatches instead of T.
    * ``draft_symbol_gen``/``draft_params`` — a small draft LM (same
      generator signature) arms speculative decoding with
      ``spec_k``/``MXNET_SERVE_SPEC_K`` proposals per verify dispatch.
    * ``prefix_cache_mb`` (or ``MXNET_SERVE_PREFIX_CACHE_MB``) — the
      byte budget for ``submit(prefix_id=...)`` cache-row reuse; pass
      0 to disable the store entirely.
    """
    window_lens = set()
    chunk = default_prefill_chunk() if prefill_chunk is None \
        else int(prefill_chunk)
    if symbol_gen is not None and chunk > 1:
        window_lens.add(chunk)
    k = default_spec_k() if spec_k is None else int(spec_k)
    draft_engine = None
    if draft_symbol_gen is not None:
        if draft_params is None:
            raise MXNetError("serve_decoder: draft_symbol_gen needs "
                             "draft_params")
        if symbol_gen is None:
            raise MXNetError(
                "serve_decoder: speculative decoding needs symbol_gen= "
                "too — the target verifies K proposals in one "
                "step_len=K window dispatch")
        window_lens.add(k)
    engine = DecodeEngine(name, symbol, arg_params, capacity=capacity,
                          ladder=ladder, context=context,
                          compute_dtype=compute_dtype, logger=logger,
                          symbol_gen=symbol_gen, window_lens=window_lens)
    if draft_symbol_gen is not None:
        draft_engine = DecodeEngine(
            name + ".draft", draft_symbol_gen(1), draft_params,
            capacity=engine.capacity, ladder=engine.ladder.sizes,
            context=context, compute_dtype=compute_dtype, logger=logger,
            symbol_gen=draft_symbol_gen, window_lens=window_lens)
    budget = None if prefix_cache_mb is None \
        else int(float(prefix_cache_mb) * (1 << 20))
    if budget is None and not engine.positional:
        budget = 0      # no default store for a state it cannot reuse
        # (one asked for by ``prefix_cache_mb`` the scheduler refuses)
    store = None
    if budget is None or budget > 0:
        store = PrefixStore(budget_bytes=budget)
        if store.budget_bytes <= 0:
            store = None
    sched = DecodeScheduler(engine, clock=clock, max_queue=max_queue,
                            default_max_new=default_max_new,
                            logger=logger, draft_engine=draft_engine,
                            prefill_chunk=chunk,
                            spec_k=k if draft_engine is not None
                            else None,
                            prefix_store=store)
    if start:
        sched.start()
    return sched
