"""Serve warm restart: checkpoint the registry, rebuild after re-exec.

The training side survives a kill because ``CheckpointManager`` owns a
versioned, atomically-committed copy of everything a resume needs. This
module closes the ROADMAP-5 remainder ("wiring serve/ warm restarts to
the same manager") by giving ``InferenceServer`` the same property: the
whole registry/ladder configuration — per model, the symbol (JSON), the
trained params (numpy), input shapes/label names, the bucket ladder,
the compute dtype — plus the server's admission/degradation settings,
rides through ``CheckpointManager.save_payload`` as a ``kind="serve"``
payload into the same atomic-commit directories (training and serving
state can share one checkpoint root; readers filter by kind).

After a crash/re-exec, :func:`restore_server` reads the newest
*readable* serve commit (the damage-tolerant fallback walk
``read_committed_payload`` provides), re-registers every model — which
re-runs warmup: compile every rung, pin the programs — and returns a
server that serves again with **zero compiles beyond warmup**: the
acceptance gate ``program_cache.compile_count()`` delta == 0 from the
post-warmup mark, the same contract a first boot makes. Requests that
were accepted-and-acked before the kill already hold their results in
their ``ResponseHandle``; queued-unacked requests fail loudly at
``stop``/death (at-most-once admission — the client retries against
the restarted server).
"""
from __future__ import annotations

import logging

import numpy as np

from ..base import MXNetError
from .engine import BucketEngine, PredictorEngine
from .server import InferenceServer

__all__ = ["save_server", "restore_server", "server_payload"]

log = logging.getLogger(__name__)


def server_payload(server):
    """The serve-state dict one commit persists (numpy/JSON only — the
    writer thread pickles it as-is)."""
    from ..checkpoint.state import FORMAT_VERSION
    models = {}
    for entry in server._registry.entries():
        eng = entry.engine
        name = eng.name
        if isinstance(eng, BucketEngine):
            arg, aux = eng._bm.get_params()
            models[name] = {
                "type": "bucket",
                "symbol": eng._symbol.tojson(),
                "arg_params": {k: v.asnumpy() for k, v in arg.items()},
                "aux_params": {k: v.asnumpy() for k, v in aux.items()},
                "data_shapes": {nm: tuple(s) for nm, s in
                                eng.example_shapes.items()},
                "label_names": list(eng._label_names),
                "ladder": list(eng.ladder.sizes),
                "compute_dtype": eng._compute_dtype,
                # int8 engines persist the ALREADY-quantized symbol +
                # params (compute_dtype is None by then), so restore
                # re-binds without re-quantizing; recorded for audit
                "quantized": getattr(eng, "quantized", None),
            }
        elif isinstance(eng, PredictorEngine) and eng._path is not None:
            models[name] = {"type": "predictor", "path": eng._path}
        else:
            log.warning(
                "serve checkpoint: model %r has no persistable source "
                "(in-memory Predictor without an artifact path); it "
                "will be missing after a warm restart", name)
    return {
        "version": FORMAT_VERSION,
        "kind": "serve",
        "cursor": {"epoch": 0, "nbatch": 0},
        "server": {
            "max_queue": server._max_queue,
            "default_deadline_ms": int(server._default_deadline_s * 1000),
            "shed_depth": server._shed_depth,
        },
        "models": models,
    }


def save_server(server, manager, block=True):
    """Commit the server's registry/config through ``manager`` (a
    ``CheckpointManager`` or a directory string); returns the seq."""
    from ..checkpoint import CheckpointManager
    owned = False
    if not isinstance(manager, CheckpointManager):
        manager = CheckpointManager(str(manager))
        owned = True
    try:
        return manager.save_payload(server_payload(server), block=block)
    finally:
        if owned:
            manager.close()


def restore_server(directory, clock=None, start=False, context=None,
                   **server_kw):
    """Rebuild an ``InferenceServer`` from the newest readable
    ``kind="serve"`` commit in ``directory``.

    Re-registering each model re-runs warmup (compile + pin every
    rung — even those compiles hit the persistent XLA cache), after
    which steady-state serving
    compiles nothing: ``compile_count()`` stays at the post-warmup
    mark. ``server_kw`` overrides the persisted server settings;
    ``context`` places the restored models (default: current device).
    """
    from ..checkpoint import read_committed_payload
    from ..ndarray import array
    from ..symbol import load_json

    found = read_committed_payload(directory, kind="serve")
    if found is None:
        raise MXNetError(
            f"no committed serve state under {directory!r} "
            "(was InferenceServer.checkpoint_to ever called?)")
    seq, path, payload = found
    saved = payload.get("server") or {}
    kw = {"max_queue": saved.get("max_queue"),
          "default_deadline_ms": saved.get("default_deadline_ms")}
    kw.update(server_kw)
    server = InferenceServer(clock=clock, **kw)
    for name, rec in (payload.get("models") or {}).items():
        if rec["type"] == "predictor":
            server.register(name, predictor=rec["path"])
            continue
        server.register(
            name,
            symbol=load_json(rec["symbol"]),
            arg_params={k: array(np.asarray(v))
                        for k, v in rec["arg_params"].items()},
            aux_params={k: array(np.asarray(v))
                        for k, v in rec["aux_params"].items()},
            data_shapes=rec["data_shapes"],
            label_names=rec["label_names"] or None,
            ladder=rec["ladder"],
            context=context,
            compute_dtype=rec.get("compute_dtype"))
    log.info("serve: warm-restarted %d model(s) from %s (seq %d)",
             len(payload.get("models") or {}), path, seq)
    if start:
        server.start()
    return server
