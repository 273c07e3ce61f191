"""Ring attention: exact attention over sequence-sharded inputs.

The reference predates attention entirely (SURVEY.md §5.7: sequence scaling
by bucketing + layer placement). This module is the framework's long-context
story: the sequence axis is sharded over the mesh's ``seq`` axis and exact
softmax attention is computed blockwise while K/V shards rotate around the
ring (``lax.ppermute`` over adjacent ICI links), overlapping each block's
FLOPs with the neighbor transfer — the Ring Attention construction
(Liu et al. 2023) on XLA collectives.

Numerics: flash-style online softmax — carry running max ``m`` and
normalizer ``l`` per query block in float32; rescale the accumulator when
the max moves. Exact (not approximate) attention for any number of shards.

Also provides the single-device reference ``attention`` and a causal
variant; tests check ring == full on an 8-device CPU mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .collectives import axis_size as _axis_size

__all__ = ["attention", "ring_attention", "ring_attention_sharded"]


def attention(q, k, v, causal=False, scale=None):
    """Plain softmax attention. q,k,v: (B, H, T, D)."""
    if scale is None:
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), dtype=bool), tk - tq)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _block_attn_update(q, k, v, m, l, acc, scale, mask=None):
    """One K/V block of online-softmax attention.

    q (B,H,Tq,D), k/v (B,H,Tk,D); m,l (B,H,Tq) float32 running max and
    normalizer; acc (B,H,Tq,D) float32 unnormalized accumulator.
    """
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    m_block = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m, m_block)
    # guard fully-masked rows (m_new == -inf)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    correction = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
    correction = jnp.where(jnp.isfinite(correction), correction, 0.0)
    l_new = l * correction + jnp.sum(p, axis=-1)
    acc_new = acc * correction[..., None] + \
        jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, axis_name="seq", causal=False, scale=None,
                   use_flash=None):
    """Exact attention with sequence-sharded q/k/v (call inside shard_map).

    Each device holds contiguous sequence shards (B, H, T/n, D). K/V blocks
    rotate around the ring; n_dev block updates produce the exact softmax.
    For ``causal=True``, blocks are masked by their absolute offset
    (device order along the axis = sequence order).

    The local block is computed by the Pallas flash kernel
    (rtc.flash_attention_partial) whenever the shard shape tiles —
    its unnormalized (acc, m, l) merges into the ring's online-softmax
    carry, so VMEM holds one K tile while FLOPs overlap the neighbor
    transfer. Auto-selected on the TPU backend; on CPU the kernel runs
    in Pallas interpret mode, which only composes with
    ``shard_map(check_vma=False)`` (as
    ``ring_attention_sharded(use_flash=True)`` arranges), so the auto
    default there is the pure-XLA block update.
    """
    T = q.shape[2]
    if use_flash is None:
        blk = min(128, T)
        use_flash = (jax.default_backend() == "tpu"
                     and T % blk == 0 and k.shape[2] == T)
    if use_flash:
        return _ring_attention_flash(q, k, v, axis_name, causal, scale)
    return _ring_attention_xla(q, k, v, axis_name, causal, scale)


def _ring_attention_flash(q, k, v, axis_name, causal, scale):
    """Ring attention with the Pallas flash kernel as the local block.

    Forward: per ring step the kernel returns the shard's unnormalized
    (acc, m, l); the carry merge is the standard two-block online-softmax
    combine. Backward: custom_vjp recomputes through the XLA ring (the
    flash recompute strategy — the kernel itself is not differentiated).
    """
    from ..rtc import flash_attention_partial

    n_dev = _axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, T, D = q.shape
    if scale is None:
        scale = 1.0 / float(D) ** 0.5
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    @jax.custom_vjp
    def run(q, k, v):
        m = jnp.full((B, H, T), -jnp.inf, dtype=jnp.float32)
        l = jnp.zeros((B, H, T), dtype=jnp.float32)
        acc = jnp.zeros((B, H, T, D), dtype=jnp.float32)
        k_blk, v_blk = k, v
        for step in range(n_dev):          # static unroll, n_dev small
            # At ring step s this device holds the shard of device
            # (my_idx - s) mod n_dev. For causal masking only the
            # relative offset matters and it has exactly two cases:
            # a past-or-present shard (my_idx >= s) at static offset
            # -s*T, or a wrapped future shard — fully masked. Keeping
            # the kernel offsets static (q_off = s*T, k_off = 0) and
            # gating the wrapped case outside keeps traced values out
            # of the Pallas scalar prefetch.
            acc_s, m_s, l_s = flash_attention_partial(
                q, k_blk, v_blk, step * T if causal else 0, 0,
                causal=causal, scale=scale)
            if causal and step > 0:
                valid = (my_idx >= step).astype(jnp.float32)
                m_s = jnp.where(valid > 0, m_s, -jnp.inf)
                l_s = l_s * valid
                acc_s = acc_s * valid
            m_new = jnp.maximum(m, m_s)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            c_old = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            c_new = jnp.where(jnp.isfinite(m_s), jnp.exp(m_s - m_safe), 0.0)
            l = l * c_old + l_s * c_new
            acc = acc * c_old[..., None] + acc_s * c_new[..., None]
            m = m_new
            if step < n_dev - 1:
                k_blk = lax.ppermute(k_blk, axis_name, perm)
                v_blk = lax.ppermute(v_blk, axis_name, perm)
        out = acc / jnp.maximum(l[..., None], 1e-20)
        return out.astype(q.dtype)

    def fwd(q, k, v):
        return run(q, k, v), (q, k, v)

    def bwd(res, ct):
        q, k, v = res
        _, vjp_fn = jax.vjp(
            lambda a, b, c: _ring_attention_xla(a, b, c, axis_name,
                                                causal, scale), q, k, v)
        return vjp_fn(ct)

    run.defvjp(fwd, bwd)
    return run(q, k, v)


def _ring_attention_xla(q, k, v, axis_name="seq", causal=False, scale=None):
    """The pure-XLA ring (also the backward recompute path)."""
    n_dev = _axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    if scale is None:
        scale = 1.0 / float(q.shape[-1]) ** 0.5
    B, H, T, D = q.shape

    m = jnp.full((B, H, T), -jnp.inf, dtype=jnp.float32)
    l = jnp.zeros((B, H, T), dtype=jnp.float32)
    acc = jnp.zeros((B, H, T, D), dtype=jnp.float32)

    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def body(step, carry):
        m, l, acc, k_blk, v_blk = carry
        src_idx = (my_idx - step) % n_dev  # which shard we hold this step
        if causal:
            q_pos = my_idx * T + jnp.arange(T)[:, None]
            k_pos = src_idx * T + jnp.arange(T)[None, :]
            mask = (q_pos >= k_pos)[None, None]
        else:
            mask = None
        m, l, acc = _block_attn_update(q, k_blk, v_blk, m, l, acc, scale,
                                       mask)
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return m, l, acc, k_nxt, v_nxt

    carry = (m, l, acc, k, v)
    for step in range(n_dev):  # unrolled: n_dev is static, small
        carry = body(step, carry)
    m, l, acc, _, _ = carry
    out = acc / jnp.maximum(l[..., None], 1e-20)
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, causal=False, seq_axis="seq",
                           use_flash=None):
    """Convenience wrapper: shard (B,H,T,D) arrays over the mesh's seq axis
    and run ring attention under shard_map.

    ``use_flash=True`` forces the Pallas-block ring even on CPU (the
    kernel then runs in interpret mode, which requires this wrapper's
    shard_map to drop vma checking)."""
    spec = P(None, None, seq_axis, None)
    kwargs = {}
    if use_flash:
        kwargs["check_vma"] = False

    from .collectives import shard_map as _shard_map
    @functools.partial(
        _shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, **kwargs)
    def run(q_s, k_s, v_s):
        return ring_attention(q_s, k_s, v_s, axis_name=seq_axis,
                              causal=causal, use_flash=use_flash)

    qs = jax.device_put(q, NamedSharding(mesh, spec))
    ks = jax.device_put(k, NamedSharding(mesh, spec))
    vs = jax.device_put(v, NamedSharding(mesh, spec))
    return run(qs, ks, vs)
