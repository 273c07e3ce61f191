"""Kernel-tier selection: per-(op, backend, shape/dtype) implementation
choice with one-shot autotuning.

The registry (ops/registry.py) keeps exactly one *semantic* definition
per op, but an op may carry alternative *implementations* — today an
XLA composition (``OpDef.forward``, always present, always correct) and
optionally a Pallas kernel (``OpDef.variants["pallas"]``). Which one
wins is an empirical, shape-dependent question, so a static "Pallas
wins" table is wrong by construction. This module makes the choice
*measured*:

* ``MXNET_KERNEL_TIER=xla``    — force the XLA composition everywhere
  (bit-exact with the pre-tier framework);
* ``MXNET_KERNEL_TIER=pallas`` — force the Pallas variant wherever one
  is registered and eligible (interpret mode off-TPU);
* ``MXNET_KERNEL_TIER=auto``   — the default: XLA everywhere except on
  a TPU backend, where the first encounter of each (op, attrs, shapes,
  dtypes) key runs a one-shot autotune — numerics-gate the Pallas
  kernel against the XLA composition, time both on device, cache the
  winner process-wide. Off-TPU, auto resolves to XLA without timing,
  so CPU results are bit-identical to ``xla``.

Under a mesh (several contexts, or ``Module.fit(spmd=True)``) dispatch
happens inside the traced runner per op, before XLA partitions the
program, and per-shape keys see the *global* logical shapes. XLA can
partition a composition but not a Mosaic kernel ("cannot be
automatically partitioned"), so a binding over more than one device
resolves XLA for every Pallas variant on a TPU backend and says so in
``decisions()``; interpret mode (the CPU test mesh, tests/test_spmd.py's
tier-parity gate) partitions like any composition and is unaffected.
The ``ring`` variant wraps its kernel in ``shard_map`` itself.

Winners are cached in-process alongside the program cache and follow
the same keying discipline (``program_cache.attr_cache_stable``: attrs
that would churn or collide a cache key make the op untunable and it
falls back to XLA). Set ``MXNET_AUTOTUNE_CACHE_DIR`` to persist
decisions as JSON keyed by (device kind, op, attrs, shapes, dtypes) so
warm restarts skip re-timing, mirroring the persistent XLA compile
cache. Every decision lands in an audit log (``decisions()``), the
``kernel_tier.*`` telemetry counters, and the flight-recorder ring.
"""
from __future__ import annotations

import json
import os
import threading
import time

from . import telemetry as _telemetry
from .base import MXNetError
from .program_cache import attr_cache_stable

__all__ = ["mode", "dispatch", "resolve", "autotune", "numerics_gate",
           "decisions", "clear", "cache_info"]

_lock = threading.Lock()
_selection = {}          # key -> variant name ("xla" | "pallas" | ...)
_decisions = []          # audit log: dicts, append order
_persist_loaded = False
_persist = {}            # str(key) -> persisted decision dict

#: per-dtype tolerance of the autotune numerics gate, applied as both
#: atol and rtol (``|got - ref| <= tol + tol * |ref|``, the allclose form
#: tests/test_decode_batch.py uses): one bf16 ulp at magnitude 4 is
#: already 3e-2, so a purely absolute bound would refuse a correct kernel
#: for how its last bit rounds. The registration-test gates in tests/ use
#: the same table.
NUMERIC_TOL = {
    "float32": 2e-4,
    "float64": 1e-8,
    "bfloat16": 2e-2,
    "float16": 1e-2,
}


def mode():
    """Current tier mode: 'xla' | 'pallas' | 'auto' (the default)."""
    m = os.environ.get("MXNET_KERNEL_TIER", "auto").lower()
    if m not in ("xla", "pallas", "auto"):
        m = "auto"
    return m


def _backend():
    import jax
    return jax.default_backend()


def _device_kind():
    import jax
    return jax.devices()[0].device_kind


def _reps():
    try:
        return max(1, int(os.environ.get("MXNET_AUTOTUNE_REPS", "5")))
    except ValueError:
        return 5


# ------------------------------------------------------------------ keys
def _attr_token(attrs):
    """Stable sorted attr tuple, or None when any attr value is not
    cache-key safe (same discipline as the program cache / RC401)."""
    items = []
    for k in sorted(attrs):
        v = attrs[k]
        ok, _why = attr_cache_stable(v)
        if not ok:
            return None
        items.append((k, tuple(v) if isinstance(v, list) else v))
    return tuple(items)


def _key(opdef, attrs, shapes, dtypes, is_train):
    tok = _attr_token(attrs)
    if tok is None:
        return None
    # the remat policy rides the key alongside the program-cache token:
    # under "all"/"dots" a kernel's forward re-executes inside the
    # backward, so a winner measured under "none" is not evidence — a
    # persisted selection must never leak across policies (the same
    # rule the fused-step program cache applies)
    from . import remat as _remat
    return (opdef.name, _backend(), tok,
            tuple(tuple(s) for s in shapes), tuple(dtypes), bool(is_train),
            ("remat", _remat.active()))


# ------------------------------------------------------ persisted winners
def _persist_path():
    d = os.environ.get("MXNET_AUTOTUNE_CACHE_DIR")
    if not d:
        return None
    return os.path.join(d, "kernel_tier.json")


def _load_persist():
    global _persist_loaded, _persist
    if _persist_loaded:
        return
    _persist_loaded = True
    path = _persist_path()
    if not path or not os.path.exists(path):
        return
    try:
        with open(path) as f:
            doc = json.load(f)
        if isinstance(doc, dict):
            _persist = {k: v for k, v in doc.items()
                        if isinstance(v, dict) and "variant" in v}
    except (OSError, ValueError):
        _persist = {}


def _save_persist():
    path = _persist_path()
    if not path:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(_persist, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass                     # persistence is best-effort


def _persist_key(key):
    # device kind (not just backend) keys the persisted file: a v5e
    # winner is not a v6e winner
    return repr((_device_kind(),) + key[:1] + key[2:])


# ---------------------------------------------------------- synth inputs
def _synth_inputs(opdef, attrs, shapes, dtypes):
    """Deterministic host-generated operands for gating/timing.

    Standard-normal (not zeros: zeros make softmax/BN degenerate and
    hide real numeric divergence), fixed seed so every process times
    the same problem. Inputs whose declared name marks them as
    second-moment state (Adam's ``var``, RMSProp's ``n``, BatchNorm's
    ``moving_var``) are made non-negative — a negative synthetic
    variance would NaN both sides and fail the gate on noise. The same
    applies to decode cursors (``*cache_pos``): a negative position
    makes the causal mask empty and softmax all -inf.
    """
    import numpy as np
    import jax.numpy as jnp
    try:
        names = list(opdef.input_names(attrs)) + \
            list(opdef.aux_names(attrs))
    except Exception:
        names = []
    rng = np.random.RandomState(0)
    vals = []
    for i, (s, dt) in enumerate(zip(shapes, dtypes)):
        a = rng.standard_normal(tuple(s)).astype("float32")
        name = names[i] if i < len(names) else ""
        if name in ("var", "n") or "var" in name.split("_") \
                or name.endswith("cache_pos"):
            a = np.abs(a)
        vals.append(jnp.asarray(a).astype(dt))
    return vals


def _run_variant(opdef, attrs, variant, regular, aux, is_train):
    """One jitted execution closure for a variant at concrete operands."""
    import jax
    fn = opdef.variant_fn(variant)
    rng = jax.random.PRNGKey(0)

    def run(r, x):
        outs, new_aux = fn(attrs, list(r), list(x), is_train, rng)
        return list(outs), list(new_aux)

    return jax.jit(run)


def numerics_gate(opdef, attrs, shapes, dtypes, variant="pallas",
                  is_train=True, n_aux=None, tol=None, inputs=None):
    """Compare a variant against the XLA composition at one shape.

    Returns ``(ok, max_abs_err)``; ``ok`` holds every element to
    ``tol + tol * |ref|``. This is the registration-test gate
    (tests call it per fused op per dtype) and the first stage of every
    autotune: a kernel that fails it can never be selected. ``inputs``
    overrides the synthetic operands (regular + aux, in order) when a
    test needs specific well-formed state.
    """
    import numpy as np
    import jax

    if n_aux is None:
        n_aux = len(opdef.aux_names(attrs))
    vals = list(inputs) if inputs is not None else \
        _synth_inputs(opdef, attrs, shapes, dtypes)
    regular = vals[:len(vals) - n_aux] if n_aux else vals
    aux = vals[len(vals) - n_aux:] if n_aux else []
    ref = _run_variant(opdef, attrs, "xla", regular, aux, is_train)(
        regular, aux)
    got = _run_variant(opdef, attrs, variant, regular, aux, is_train)(
        regular, aux)
    if tol is None:
        tol = max(NUMERIC_TOL.get(str(dt), 2e-4) for dt in dtypes)
    ok, max_err = True, 0.0
    for side_r, side_g in zip(ref, got):
        for r, g in zip(side_r, side_g):
            r = np.asarray(jax.device_get(r), dtype="float32")
            err = np.abs(r - np.asarray(jax.device_get(g),
                                        dtype="float32"))
            max_err = max(max_err, float(np.max(err)))
            # not (err <= bound): a NaN on either side must fail
            ok = ok and not np.any(~(err <= tol + tol * np.abs(r)))
    return ok, max_err


def _time_variant(run, regular, aux, reps):
    import jax
    out = run(regular, aux)                        # compile + warm
    jax.block_until_ready(out)
    laps = []
    # benchmark timing is the one legitimate wall-clock read here: the
    # laps are the measurement itself, not a replayable decision input
    for _ in range(reps):
        tic = time.perf_counter()  # mxlint: allow(DT401)
        jax.block_until_ready(run(regular, aux))
        laps.append(time.perf_counter() - tic)  # mxlint: allow(DT401)
    laps.sort()
    return laps[len(laps) // 2]


def autotune(opdef, attrs, shapes, dtypes, is_train):
    """Measure pallas vs xla at one key; returns (winner, record).

    A numerics-gate miss and "measured slower" resolve to "xla" with the
    reason recorded. A kernel the compiler refuses (Mosaic lowering or
    compile error) is a defect of a registered variant, not a selection
    outcome: it raises, naming op, shapes and dtypes.

    ``resolve`` reaches this from inside the trace of the program that
    contains the op, so the measurement runs under
    ``jax.core.eval_context``: the synthetic operands are concrete and
    both variants execute on the device instead of being staged into the
    enclosing trace.
    """
    import jax
    n_aux = len(opdef.aux_names(attrs))
    rec = {"op": opdef.name, "shapes": [list(s) for s in shapes],
           "dtypes": [str(d) for d in dtypes], "is_train": bool(is_train),
           "backend": _backend()}
    try:
        with jax.core.eval_context():
            ok, err = numerics_gate(opdef, attrs, shapes, dtypes,
                                    is_train=is_train, n_aux=n_aux)
            rec["max_abs_err"] = err
            if not ok:
                rec.update(variant="xla", reason="numerics-gate failed")
                return "xla", rec
            vals = _synth_inputs(opdef, attrs, shapes, dtypes)
            regular = vals[:len(vals) - n_aux] if n_aux else vals
            aux = vals[len(vals) - n_aux:] if n_aux else []
            reps = _reps()
            t_xla = _time_variant(
                _run_variant(opdef, attrs, "xla", regular, aux, is_train),
                regular, aux, reps)
            t_pl = _time_variant(
                _run_variant(opdef, attrs, "pallas", regular, aux,
                             is_train),
                regular, aux, reps)
    except Exception as e:
        raise MXNetError(
            f"kernel_tier.autotune: the pallas variant of {opdef.name} "
            f"failed at shapes={rec['shapes']} dtypes={rec['dtypes']} "
            f"is_train={bool(is_train)}: {type(e).__name__}: {e}") from e
    rec["xla_ms"] = round(t_xla * 1e3, 4)
    rec["pallas_ms"] = round(t_pl * 1e3, 4)
    if t_pl < t_xla:
        rec.update(variant="pallas",
                   reason=f"measured {t_xla / t_pl:.2f}x faster")
        return "pallas", rec
    rec.update(variant="xla",
               reason=f"pallas measured {t_pl / t_xla:.2f}x slower")
    return "xla", rec


def _note_decision(rec, source):
    rec = dict(rec, source=source)
    with _lock:
        _decisions.append(rec)
    _telemetry.counter("kernel_tier.selection", op=rec["op"],
                       variant=rec.get("variant", "xla")).inc()
    _telemetry.flightrec.note("kernel_tier.decision", op=rec["op"],
                              variant=rec.get("variant", "xla"),
                              source=source,
                              reason=rec.get("reason", ""))


# -------------------------------------------------------------- selection
_ring_noted = set()       # plan/mesh decisions already audit-logged


def _resolve_ring(opdef, attrs, shapes, dtypes, spmd_plan):
    """Plan-driven lowering: when the binding's SpmdPlan carries a
    nonempty ``seq`` mesh axis and the op registers a ``ring`` variant
    that is eligible at these shapes, the sequence-sharded ring
    lowering wins — the whole point of sharding the sequence axis.
    ``MXNET_KERNEL_TIER=xla`` still forces compositions everywhere
    (the bit-exact contract), handled by the caller."""
    if spmd_plan is None or "ring" not in opdef.variants:
        return None
    try:
        n_seq = int(spmd_plan.n_seq_shards())
    except Exception:
        return None
    if n_seq <= 1:
        return None
    from .parallel import spmd as _spmd_mod
    with _spmd_mod.plan_scope(spmd_plan):
        if not opdef.variant_eligible("ring", attrs, shapes, dtypes):
            return None
    note_key = (opdef.name, tuple(tuple(s) for s in shapes),
                tuple(dtypes))
    if note_key not in _ring_noted:
        _ring_noted.add(note_key)
        _note_decision(
            {"op": opdef.name, "variant": "ring",
             "shapes": [list(s) for s in shapes],
             "dtypes": [str(d) for d in dtypes],
             "backend": _backend(),
             "reason": f"sequence-sharded plan (seq={n_seq}): ring "
                       "attention over lax.ppermute"},
            source="plan")
    return "ring"


def _note_unpartitionable(opdef, shapes, dtypes, n_devices):
    note_key = (opdef.name, tuple(tuple(s) for s in shapes), tuple(dtypes),
                n_devices)
    if note_key in _ring_noted:
        return
    _ring_noted.add(note_key)
    _note_decision(
        {"op": opdef.name, "variant": "xla",
         "shapes": [list(s) for s in shapes],
         "dtypes": [str(d) for d in dtypes], "backend": _backend(),
         "reason": f"binding spans {n_devices} devices: XLA cannot "
                   "partition a Mosaic kernel"},
        source="mesh")


def resolve(opdef, attrs, shapes, dtypes, is_train, spmd_plan=None,
            n_devices=1):
    """Variant name for one (op, attrs, shapes, dtypes, train) site of a
    program bound over ``n_devices`` devices."""
    m = mode()
    if m != "xla":
        ring = _resolve_ring(opdef, attrs, shapes, dtypes, spmd_plan)
        if ring is not None:
            return ring
    if m == "xla" or not opdef.variants or "pallas" not in opdef.variants:
        return "xla"
    if n_devices > 1:
        from .ops.pallas_kernels import _interpret
        if not _interpret():
            _note_unpartitionable(opdef, shapes, dtypes, n_devices)
            return "xla"
    if m == "pallas":
        return "pallas" if opdef.variant_eligible(
            "pallas", attrs, shapes, dtypes) else "xla"
    # auto: Pallas is eligible only on a TPU backend, and only after
    # winning its one-shot per-shape measurement
    if _backend() != "tpu" or not opdef.variant_eligible(
            "pallas", attrs, shapes, dtypes):
        return "xla"
    key = _key(opdef, attrs, shapes, dtypes, is_train)
    if key is None:
        return "xla"             # uncacheable attrs: never autotune
    with _lock:
        hit = _selection.get(key)
    if hit is not None:
        _telemetry.counter("kernel_tier.cache.hit").inc()
        return hit
    _telemetry.counter("kernel_tier.cache.miss").inc()
    _load_persist()
    pkey = _persist_key(key)
    prec = _persist.get(pkey)
    if prec is not None:
        winner = prec["variant"]
        _note_decision(prec, source="persisted")
    else:
        _telemetry.counter("kernel_tier.autotune.runs").inc()
        winner, rec = autotune(opdef, attrs, shapes, dtypes, is_train)
        _note_decision(rec, source="autotune")
        with _lock:
            _persist[pkey] = {k: rec[k] for k in
                              ("op", "variant", "reason", "shapes",
                               "dtypes", "is_train") if k in rec}
            for k in ("xla_ms", "pallas_ms", "max_abs_err"):
                if k in rec:
                    _persist[pkey][k] = rec[k]
        _save_persist()
    with _lock:
        _selection[key] = winner
    return winner


def dispatch(opdef, attrs, inputs, aux, is_train, rng, spmd_plan=None,
             n_devices=1):
    """Run one op through the tier; the single choke point both the
    executor's graph runner and imperative invoke call instead of
    ``opdef.forward``. Zero-variant ops pass straight through.
    ``spmd_plan`` (the binding's SpmdPlan, threaded from the executor)
    arms plan-driven lowerings — the ring variant runs inside a
    ``plan_scope`` so it can read the mesh/axes. ``n_devices`` is the
    number of devices the program is bound over."""
    if not opdef.variants:
        return opdef.forward(attrs, inputs, aux, is_train, rng)
    shapes = [tuple(v.shape) for v in inputs] + \
        [tuple(v.shape) for v in aux]
    dtypes = [str(v.dtype) for v in inputs] + [str(v.dtype) for v in aux]
    variant = resolve(opdef, attrs, shapes, dtypes, is_train,
                      spmd_plan=spmd_plan, n_devices=n_devices)
    fn = opdef.variant_fn(variant)
    if variant == "ring" and spmd_plan is not None:
        from .parallel import spmd as _spmd_mod
        with _spmd_mod.plan_scope(spmd_plan):
            return fn(attrs, inputs, aux, is_train, rng)
    return fn(attrs, inputs, aux, is_train, rng)


# ------------------------------------------------------------- inspection
def decisions():
    """Audit log of every selection decision this process made."""
    with _lock:
        return [dict(r) for r in _decisions]


def cache_info():
    with _lock:
        return {"selections": len(_selection),
                "decisions": len(_decisions),
                "persisted": len(_persist)}


def clear():
    """Drop in-memory selections + audit log (tests). The persisted
    file, if any, is left on disk; it reloads on the next resolve."""
    global _persist_loaded
    with _lock:
        _selection.clear()
        del _decisions[:]
        _persist.clear()
        _ring_noted.clear()
    _persist_loaded = False
