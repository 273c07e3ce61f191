"""Serving engines: pre-compiled bucket programs behind one forward().

Two backends, one contract — ``forward(bucket, values)`` runs the
pre-compiled program for one ladder rung over an assembled (already
padded) batch and returns the output NDArrays:

* ``BucketEngine`` — symbol + params. Internally a ``BucketingModule``
  whose bucket key IS the batch size: every rung is a Module bound
  ``for_training=False`` over a ``shared_module`` leader, so all rungs
  alias ONE set of parameter cells and each rung's forward program
  lands in the process-wide program cache under the normal executor
  keys. The inference forward path never donates an input (arguments
  are never in ``donate_argnums``), so a batch assembled from caller
  arrays is never invalidated by dispatch — the donation-safe batched
  forward. What a ``fwd_infer`` program may take over is the graph's
  own aux state, and only where an op asks for it
  (``OpDef.donate_aux``: the decode ops' cache pools, served by
  ``serve/decode.py``); a graph without such an op, which is what this
  engine serves, donates nothing.
* ``PredictorEngine`` — an exported ``.mxp`` artifact served directly
  (predict.py): the ladder is the artifact's fixed exported batch size
  (re-export to change it) and the program is the deserialized
  StableHLO executable, no Symbol/Module stack in the process.

``warmup(clock)`` traces/compiles every rung (two forwards: the first
pays compile, the second measures steady-state execution on the given
clock — a FakeClock measures 0, which the deterministic scheduler tests
rely on), pins each rung's program in the program cache so a later
training rebind storm cannot evict a serving program, and records the
compile delta. After warmup, ``compiles_since_warmup()`` must stay 0 —
the acceptance contract the e2e test asserts.
"""
from __future__ import annotations

import logging

import numpy as np

from .. import program_cache as _progcache
from ..base import MXNetError
from ..io import DataBatch, DataDesc
from ..ndarray import NDArray
from .batching import BucketLadder

__all__ = ["BucketEngine", "PredictorEngine"]

log = logging.getLogger(__name__)


class _EngineBase:
    """Shared ladder/shape validation + warmup accounting."""

    def __init__(self, name, ladder):
        self.name = name
        self.ladder = ladder if isinstance(ladder, BucketLadder) \
            else BucketLadder(ladder)
        self.exec_est = {}            # bucket -> measured seconds (EMA'd
        self._warm_mark = None        # by the scheduler via note_exec)
        self.warmup_compiles = None

    # -- contract pieces subclasses fill in
    data_names = ()
    example_shapes = {}               # name -> per-row shape
    input_dtypes = {}                 # name -> numpy dtype

    def validate(self, inputs):
        """(rows, canonical dict) for one request's inputs; raises on a
        shape/name mismatch so bad requests fail at submit, not in the
        dispatch thread."""
        rows = None
        vals = {}
        for nm in self.data_names:
            if nm not in inputs:
                raise MXNetError(f"model {self.name!r}: missing input "
                                 f"{nm!r} (needs {list(self.data_names)})")
            arr = np.asarray(inputs[nm], dtype=self.input_dtypes[nm])
            want = self.example_shapes[nm]
            if arr.ndim != len(want) + 1 or tuple(arr.shape[1:]) != want:
                raise MXNetError(
                    f"model {self.name!r} input {nm!r}: shape "
                    f"{tuple(arr.shape)} != (rows,)+{want}")
            if rows is None:
                rows = arr.shape[0]
            elif arr.shape[0] != rows:
                raise MXNetError(
                    f"model {self.name!r}: inputs disagree on rows "
                    f"({rows} vs {arr.shape[0]} for {nm!r})")
            vals[nm] = arr
        if rows is None or rows < 1:
            raise MXNetError(f"model {self.name!r}: empty request")
        if rows > self.ladder.max:
            raise MXNetError(
                f"model {self.name!r}: {rows} rows exceed the largest "
                f"bucket {self.ladder.max} (extend the ladder or split "
                "the request)")
        return rows, vals

    def note_exec(self, bucket, seconds):
        """EMA the measured execution time into the flush estimate."""
        prev = self.exec_est.get(bucket)
        self.exec_est[bucket] = seconds if prev is None else \
            0.7 * prev + 0.3 * seconds

    def exec_estimate(self, bucket):
        """Execution-seconds estimate for a rung (0 until measured)."""
        if bucket in self.exec_est:
            return self.exec_est[bucket]
        known = [v for v in self.exec_est.values()]
        return max(known) if known else 0.0

    def warmup(self, clock):
        """Compile every rung, measure steady-state exec, pin programs."""
        mark = _progcache.compile_count()
        for bucket in self.ladder:
            zeros = {nm: np.zeros((bucket,) + self.example_shapes[nm],
                                  dtype=self.input_dtypes[nm])
                     for nm in self.data_names}
            self.forward(bucket, zeros)          # trace + compile
            t0 = clock.now()
            outs = self.forward(bucket, zeros)   # steady state
            for o in outs:
                np.asarray(o.asnumpy())          # force completion
            self.exec_est[bucket] = max(0.0, clock.now() - t0)
        self._pin_programs()
        self._warm_mark = _progcache.compile_count()
        self.warmup_compiles = self._warm_mark - mark
        return dict(self.exec_est)

    def compiles_since_warmup(self):
        """Fresh program-cache insertions since warmup finished (must be
        0 in steady state), or None before warmup."""
        if self._warm_mark is None:
            return None
        return _progcache.compile_count() - self._warm_mark

    def _pin_programs(self):
        pass

    def program_keys(self):
        """Process-cache keys of this engine's rung programs (may be
        empty for program stores outside the cache, e.g. Predictor)."""
        return []

    def programs_resident(self):
        """All rung programs still live in the process cache?"""
        keys = self.program_keys()
        return all(_progcache.contains(k) for k in keys) if keys else True


class BucketEngine(_EngineBase):
    """Symbol+params serving over a batch-size bucket ladder."""

    def __init__(self, name, symbol, arg_params, aux_params, data_shapes,
                 label_names=("softmax_label",), ladder=None, context=None,
                 compute_dtype=None, logger=None):
        """``data_shapes``: dict input name -> per-ROW shape (no batch
        dim) or list of ``(name, per_row_shape)``; the ladder supplies
        the batch dims. ``label_names`` are the loss-head inputs left
        unbound in inference mode (Module.predict semantics)."""
        super().__init__(name, ladder)
        from ..context import current_context
        from ..module import BucketingModule

        # compute_dtype="int8" / "fp8" selects a quantized inference
        # tier: the symbol is rewritten onto the Quantized* ops and
        # every dense/conv weight splits into a narrow storage cell
        # (int8 or float8_e4m3fn) + per-channel f32 scales
        # (ops/quant.py) BEFORE binding, so each ladder rung pins a
        # quantized program and the warm-restart payload (serve/
        # warm.py) persists the already-quantized symbol+params —
        # restores rebuild without re-quantizing. Activations stay
        # float; outputs sit within quant.INT8_TOL / quant.FP8_TOL of
        # the float ladder.
        self.quantized = None
        if compute_dtype is not None and str(compute_dtype) in (
                "int8", "fp8", "float8_e4m3fn"):
            from ..ops import quant as _quant
            symbol, arg_params = _quant.quantize_symbol(
                symbol, dict(arg_params or {}), dtype=str(compute_dtype))
            self.quantized = str(compute_dtype)
            compute_dtype = None

        if isinstance(data_shapes, dict):
            data_shapes = list(data_shapes.items())
        self.data_names = tuple(nm for nm, _ in data_shapes)
        self.example_shapes = {nm: tuple(s) for nm, s in data_shapes}
        self._symbol = symbol
        self._compute_dtype = compute_dtype     # for warm-restart payloads
        self._label_names = [nm for nm in (label_names or [])
                             if nm in symbol.list_arguments()]
        self._label_shape_cache = {}
        self._context = context if context is not None else current_context()

        # bucket key == batch size; every rung shares the leader's
        # parameter cells (shared_module bind) and its own cached
        # forward program
        self._bm = BucketingModule(
            sym_gen=lambda bucket: (symbol, list(self.data_names),
                                    list(self._label_names)),
            default_bucket_key=self.ladder.max,
            logger=logger or log, context=self._context)
        # BucketingModule's Module kwargs don't carry compute_dtype;
        # thread it through the per-bucket Module constructor args
        if compute_dtype is not None:
            self._bm._module_kwargs["compute_dtype"] = compute_dtype
        # loss-head labels are bound per bucket (zero-filled, ignored by
        # inference) — leaving label_shapes=None would classify the
        # label as a shared PARAM cell and alias the leader's
        # batch-sized label array into every rung
        self._bm.bind(self._provide_data(self.ladder.max),
                      label_shapes=self._provide_label(self.ladder.max),
                      for_training=False)
        self._bm.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params)
        self._bm.warm_buckets(
            [(b, self._provide_data(b), self._provide_label(b))
             for b in self.ladder])

        # recorded input dtypes come from the bound arrays (what the
        # compiled program actually takes — bf16 under compute_dtype)
        leader = self._bm._buckets[self.ladder.max]
        arg_dict = leader._exec_group.executor.arg_dict
        self.input_dtypes = {
            nm: np.dtype(str(arg_dict[nm].dtype)) if nm in arg_dict
            else np.float32
            for nm in self.data_names}

    def _provide_data(self, bucket):
        return [DataDesc(nm, (bucket,) + self.example_shapes[nm],
                         dtype=self.input_dtypes.get(nm, np.float32))
                for nm in self.data_names]

    def _provide_label(self, bucket):
        """Label shapes for one rung, inferred from the symbol against
        the rung's data shapes (None when the head has no label)."""
        if not self._label_names:
            return None
        if bucket not in self._label_shape_cache:
            known = {nm: (bucket,) + self.example_shapes[nm]
                     for nm in self.data_names}
            inferred, _, _ = self._symbol.infer_shape(**known)
            by_name = dict(zip(self._symbol.list_arguments(), inferred))
            self._label_shape_cache[bucket] = [
                DataDesc(nm, by_name[nm]) for nm in self._label_names
                if by_name.get(nm) is not None]
        return self._label_shape_cache[bucket] or None

    def forward(self, bucket, values):
        """Run the bucket program over one assembled batch (``values``:
        name -> array with exactly ``bucket`` rows)."""
        if bucket not in self.ladder.sizes:
            raise MXNetError(f"model {self.name!r}: {bucket} is not a "
                             f"ladder rung {self.ladder.sizes}")
        batch = DataBatch(
            data=[NDArray(np.ascontiguousarray(values[nm]),
                          ctx=self._context)
                  for nm in self.data_names],
            label=None, bucket_key=bucket,
            provide_data=self._provide_data(bucket),
            provide_label=self._provide_label(bucket))
        self._bm.forward(batch, is_train=False)
        return self._bm.get_outputs()

    @property
    def output_names(self):
        return self._bm._leader.output_names

    def program_keys(self):
        keys = []
        for bucket, mod in self._bm._buckets.items():
            key = mod._exec_group.executor.program_cache_key("fwd_infer")
            if key is not None:
                keys.append(key)
        return keys

    def _pin_programs(self):
        for key in self.program_keys():
            if not _progcache.pin(key):
                log.warning("serve %r: bucket program not resident at "
                            "pin time (cache capacity too small for the "
                            "ladder? MXNET_PROGRAM_CACHE_SIZE)", self.name)


class PredictorEngine(_EngineBase):
    """Serve an exported ``.mxp`` artifact directly (predict.py).

    The exported program's shapes are fixed at export time, so the
    ladder is the single exported batch size; requests pad into it.
    Re-export at other batch sizes (or use ``BucketEngine``) for a
    multi-rung ladder.
    """

    def __init__(self, name, predictor, ladder=None):
        from ..predict import Predictor
        # keep the artifact path (when there is one) so warm restarts
        # can re-register this engine from disk (serve/warm.py)
        self._path = predictor if isinstance(predictor, str) \
            else getattr(predictor, "_path", None)
        if isinstance(predictor, str):
            predictor = Predictor(predictor)
        self._pred = predictor
        shapes = predictor.input_shapes
        batches = {s[0] for s in shapes.values()}
        if len(batches) != 1:
            raise MXNetError(
                f"model {name!r}: exported inputs disagree on the batch "
                f"dim ({sorted(batches)}); cannot derive a bucket")
        exported = batches.pop()
        if ladder is not None and list(BucketLadder(ladder)) != [exported]:
            raise MXNetError(
                f"model {name!r}: a .mxp artifact serves only its "
                f"exported batch size {exported}; re-export to change "
                "the ladder")
        super().__init__(name, [exported])
        self.data_names = tuple(shapes)
        self.example_shapes = {nm: tuple(s[1:])
                               for nm, s in shapes.items()}
        self.input_dtypes = {nm: np.dtype(predictor.input_dtypes.get(
            nm, "float32")) for nm in shapes}

    def forward(self, bucket, values):
        if bucket != self.ladder.max:
            raise MXNetError(f"model {self.name!r}: exported batch is "
                             f"{self.ladder.max}, got bucket {bucket}")
        return self._pred.forward(**values)

    def warmup(self, clock):
        """Base warmup, then rewind a stateful artifact's carried state:
        the warmup forwards advance a KV-cache decoder's cache with
        zero-token garbage, and served decode steps must start from the
        exported snapshot. A stateful engine also opens its decode
        *session trace* here: every submit against it joins ONE trace
        (telemetry.trace), so an N-token decode reconstructs to a
        single span tree under the session root."""
        est = super().warmup(clock)
        if getattr(self._pred, "stateful", False):
            self._pred.reset_state()
            from ..telemetry import trace as _trace
            self.session_trace = _trace.new_trace(session=True)
        return est

    def reset_session(self):
        """Rewind the decoder state AND rotate the session trace — the
        next submit starts a fresh decode session/tree."""
        if getattr(self._pred, "stateful", False):
            self._pred.reset_state()
            from ..telemetry import trace as _trace
            self.session_trace = _trace.new_trace(session=True)

    @property
    def output_names(self):
        return self._pred.output_names
