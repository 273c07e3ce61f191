"""Rematerialization (checkpoint) policy for the fused/K-step programs.

The fused train step (executor_group.setup_fused_step) differentiates
the whole forward with ``jax.vjp``, so every intermediate the backward
needs is *saved* between the forward and backward halves of the one XLA
program — the classic activation-memory bill. ZeRO and the memory
accountant freed HBM elsewhere; this knob converts that headroom into
larger batches by shrinking the saved-residual set:

* ``none`` — no rematerialization (the default; programs are identical
  to the pre-knob framework, bit for bit);
* ``dots`` — ``jax.checkpoint`` with the ``dots_saveable`` policy: the
  matmul/conv outputs stay saved (recomputing them would re-pay MXU
  time), everything elementwise between them — BN normalize chains,
  activations, dropout masks — is recomputed during backward from the
  saved dot outputs. The usual sweet spot: memory-bound intermediates
  vanish from the residual set at near-zero recompute FLOPs;
* ``all`` — full rematerialization: only the program *inputs* are
  saved and the whole forward replays inside the backward (~1/3 extra
  FLOPs for convnets, maximum residual savings).

Selection: ``Module.fit(remat="dots")`` > ``MXNET_REMAT_POLICY`` env >
``"none"``. The active policy is part of every program-cache key the
fused/scan steps mint AND of the kernel-tier autotune key (a kernel
measured under ``none`` may lose under ``all``, where its recompute
runs twice — a persisted selection must never leak across policies).

The policy also arms **donation of the step's eval-only
intermediates**: the rng key chain and (when the graph's training
forward refreshes every aux entry — BatchNorm does) the aux-state
buffers are donated to the fused program, since both are replaced by
same-shaped outputs each step and nothing outside the step reads the
stale buffer afterwards. Under ``none`` the donation set stays exactly
the pre-knob (params, optimizer states) so existing bindings are
untouched.

``residual_bytes`` measures what the policy actually buys: the total
bytes of the VJP residual set at trace time (``jax.eval_shape`` over
``jax.vjp`` — no execution, backend-independent). The memory accountant
uses it to gate that a policy drops peak live bytes enough to admit the
next-larger batch bucket (``telemetry.memory.batch_headroom``).
"""
from __future__ import annotations

import math
import os

__all__ = ["POLICIES", "DOT_SAVEABLE_OPS", "resolve", "active",
           "set_active", "wrap", "residual_bytes"]

POLICIES = ("none", "dots", "all")

#: static mirror of ``jax.checkpoint_policies.dots_saveable`` at the op
#: level: ops whose outputs come off the MXU (dot_general / conv
#: primitives) and therefore STAY SAVED under the ``dots`` policy while
#: everything elementwise between them is recomputed. The static memory
#: planner (analysis/memplan.py) folds output bytes of exactly these
#: ops to predict the ``dots`` residual set without tracing; keep the
#: set in sync with the saveable primitives when jax's policy changes.
DOT_SAVEABLE_OPS = frozenset({
    "Convolution", "Deconvolution", "FullyConnected", "dot", "batch_dot",
    "FusedConvBNReLU", "QuantizedFullyConnected", "QuantizedConvolution",
    "RNN", "attention", "pallas_flash_attention",
})

_override = None        # fit(remat=...) pins the process-wide policy


def _env_policy():
    p = os.environ.get("MXNET_REMAT_POLICY", "none").lower()
    return p if p in POLICIES else "none"


def resolve(explicit=None):
    """Validate + resolve one policy request: explicit > env > none."""
    if explicit is None:
        return active()
    p = str(explicit).lower()
    if p not in POLICIES:
        raise ValueError(
            f"remat policy {explicit!r}: expected one of {POLICIES}")
    return p


def active():
    """The process-wide policy (cache-key token): the ``fit(remat=...)``
    override when one was set, else ``MXNET_REMAT_POLICY``."""
    return _override if _override is not None else _env_policy()


def set_active(policy):
    """Pin the process-wide policy (``None`` returns to env-driven)."""
    global _override
    _override = None if policy is None else resolve(policy)
    return active()


def wrap(f, policy):
    """Apply one policy to a differentiable callable (the fused step's
    forward closure). ``none`` is the identity — the traced program is
    unchanged down to the jaxpr."""
    import jax
    if policy == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.dots_saveable)
    if policy == "all":
        return jax.checkpoint(f)
    return f


def residual_bytes(f, *args):
    """Bytes of the VJP residual set of ``f`` at ``args`` — the
    activations stored between the forward and backward halves, the
    quantity a remat policy shrinks. Pure trace (``jax.make_jaxpr``):
    nothing executes, so the number is exact and backend-independent.

    Each saved value counts once. Under ``jax.checkpoint`` a nested
    ``jit`` (``jnp.var`` inside BatchNorm) hands its own input back as
    a residual of its known half (jax 0.9.0), so a conv output saved by
    ``dots_saveable`` appears a second time in the residual list; in the
    compiled step that is one buffer, and it is counted as one here.
    """
    import jax
    from jax.extend.core import Var

    def res(*a):
        _out, vjp_fn = jax.vjp(f, *a)
        return vjp_fn            # a pytree whose leaves ARE the residuals

    jaxpr = jax.make_jaxpr(res)(*args).jaxpr
    producer = {v: eqn for eqn in jaxpr.eqns for v in eqn.outvars}

    def source(v):
        # follow a value handed back unchanged by a nested jit to the
        # variable that went in
        while v in producer and "jaxpr" in producer[v].params:
            eqn = producer[v]
            inner = eqn.params["jaxpr"].jaxpr
            iv = inner.outvars[eqn.outvars.index(v)]
            if iv not in inner.invars:
                break
            v = eqn.invars[inner.invars.index(iv)]
        return v

    total = 0
    seen = set()
    for v in jaxpr.outvars:
        if isinstance(v, Var):
            v = source(v)
            if v in seen:
                continue
            seen.add(v)
        total += math.prod(v.aval.shape) * v.aval.dtype.itemsize
    return total
