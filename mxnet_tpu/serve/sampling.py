"""Token sampling for the decode plane: temperature / top-k / top-p
with a recorded per-request rng chain, plus the exact speculative
rejection rule.

What crosses to the host is token ids, not logits: behind every step
program the scheduler launches one small select program (``models.
transformer.BatchedKVCacheDecoder.select_rows``) that picks each slot's
last fed logits row and takes its argmax on the device - the first
maximum, as ``np.argmax`` - and a greedy request takes that id. Every
other request is sampled HOST-side, here, on its logits row: the
selected rows are fetched besides the ids whenever a slot that samples
in the iteration is not greedy. The select program is the same
whatever the requests' sampling parameters and the step programs stay
sampling-free, so arming temperature/top-k/top-p (or switching a
request between them) never mints a trace. Determinism contract:

* every request owns one ``numpy`` PCG64 chain seeded by
  ``SamplingParams.seed`` — draws happen in a fixed order (draft
  proposals first, then verify, one uniform per decision), and greedy
  decisions consume NO draws (so a greedy run is bit-identical whether
  or not a seed was set);
* the math is float64 end-to-end (softmax, filters, inverse-CDF), so
  replaying the same logits bytes through the same chain reproduces the
  same token bytes on any host;
* ``speculative_verify`` implements the exact rejection rule (accept
  ``d`` with prob ``min(1, p(d)/q(d))``; on reject sample the residual
  ``max(p - q, 0)``), which makes accepted output distributionally
  identical to target-only sampling — and bit-identical under greedy.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError

__all__ = ["SamplingParams", "token_probs", "sample_from",
           "sample_token", "speculative_verify"]


class SamplingParams:
    """Per-request sampling policy. ``temperature=0`` is greedy-argmax
    (the default — byte-compatible with the pre-sampling scheduler);
    ``top_k``/``top_p`` filter the distribution before the draw.
    ``seed`` seeds the request's rng chain — resubmitting the same
    prompt with the same params replays the same token stream byte for
    byte (the trace-plane replay contract).

    **The denoising parameters of a request** to a model that decodes
    by blocks (``serve.decode``; each None: the graph's own default,
    ``models.transformer.decode_procedure``): ``denoising_steps``, the
    feeds a block's positions are decided in (1 to the block's length;
    a feed decides ``length / steps`` positions, the remainder in the
    first feeds); ``remasking``, which positions a feed decides -
    ``"low_confidence_static"`` the quota's most confident,
    ``"low_confidence_dynamic"`` those and every other whose confidence
    passes ``confidence_threshold``. Such a request is greedy: the
    positions are decided on the device, by argmax. An engine that
    decodes one token a step refuses a request that sets any."""

    __slots__ = ("temperature", "top_k", "top_p", "seed",
                 "denoising_steps", "remasking", "confidence_threshold")

    def __init__(self, temperature=0.0, top_k=0, top_p=1.0, seed=0,
                 denoising_steps=None, remasking=None,
                 confidence_threshold=None):
        temperature = float(temperature)
        top_k = int(top_k)
        top_p = float(top_p)
        if temperature < 0.0:
            raise MXNetError(f"temperature {temperature} must be >= 0")
        if top_k < 0:
            raise MXNetError(f"top_k {top_k} must be >= 0 (0 = off)")
        if not 0.0 < top_p <= 1.0:
            raise MXNetError(f"top_p {top_p} must be in (0, 1]")
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.seed = int(seed)
        if denoising_steps is not None and int(denoising_steps) < 1:
            raise MXNetError(f"denoising_steps {denoising_steps} must be "
                             ">= 1 (a feed decides at least one position)")
        if remasking not in (None, "low_confidence_static",
                             "low_confidence_dynamic"):
            raise MXNetError(
                f"remasking {remasking!r}: 'low_confidence_static' or "
                "'low_confidence_dynamic'")
        if confidence_threshold is not None \
                and not 0.0 <= float(confidence_threshold) <= 1.0:
            raise MXNetError(f"confidence_threshold {confidence_threshold} "
                             "must be in [0, 1]")
        self.denoising_steps = None if denoising_steps is None \
            else int(denoising_steps)
        self.remasking = remasking
        self.confidence_threshold = None if confidence_threshold is None \
            else float(confidence_threshold)

    @property
    def greedy(self):
        return self.temperature == 0.0

    @property
    def denoises(self):
        """Does the request set a denoising parameter?"""
        return not (self.denoising_steps is None and self.remasking is None
                    and self.confidence_threshold is None)

    def make_rng(self):
        """The request's recorded rng chain: reseeding reproduces every
        draw in order."""
        return np.random.Generator(np.random.PCG64(self.seed))

    def __repr__(self):
        more = "" if not self.denoises else (
            f", denoising_steps={self.denoising_steps}, "
            f"remasking={self.remasking!r}, "
            f"confidence_threshold={self.confidence_threshold}")
        return (f"SamplingParams(temperature={self.temperature}, "
                f"top_k={self.top_k}, top_p={self.top_p}, "
                f"seed={self.seed}{more})")


def token_probs(logits, params):
    """One logits row -> the f64 token distribution ``params`` samples
    from (greedy -> one-hot at the argmax; otherwise tempered softmax
    with top-k then top-p filtering, renormalized). The speculative
    verifier needs the full vector, not just a draw."""
    logits = np.asarray(logits, np.float64).reshape(-1)
    if params.greedy:
        probs = np.zeros(logits.shape[0], np.float64)
        probs[int(np.argmax(logits))] = 1.0
        return probs
    z = logits / params.temperature
    z -= z.max()
    probs = np.exp(z)
    probs /= probs.sum()
    if params.top_k and params.top_k < probs.shape[0]:
        # keep the k largest; ties at the boundary resolve by index
        # order (np.argsort stable on the negated copy) — deterministic
        keep = np.argsort(-probs, kind="stable")[:params.top_k]
        mask = np.zeros(probs.shape[0], bool)
        mask[keep] = True
        probs = np.where(mask, probs, 0.0)
        probs /= probs.sum()
    if params.top_p < 1.0:
        order = np.argsort(-probs, kind="stable")
        csum = np.cumsum(probs[order])
        # minimal prefix reaching top_p mass (>= keeps at least one)
        cut = int(np.searchsorted(csum, params.top_p, side="left")) + 1
        mask = np.zeros(probs.shape[0], bool)
        mask[order[:cut]] = True
        probs = np.where(mask, probs, 0.0)
        probs /= probs.sum()
    return probs


def sample_from(probs, u):
    """Inverse-CDF draw: one uniform ``u`` in [0, 1) against an
    (unnormalized-ok) f64 weight vector."""
    cdf = np.cumsum(np.asarray(probs, np.float64))
    total = cdf[-1]
    if total <= 0.0:
        raise MXNetError("sample_from: all-zero weight vector")
    return int(min(np.searchsorted(cdf, u * total, side="right"),
                   cdf.shape[0] - 1))


def sample_token(logits, params, rng):
    """Sample one token from a logits row. Greedy consumes no rng
    draw; everything else consumes exactly one uniform."""
    if params.greedy:
        return int(np.argmax(np.asarray(logits)))
    return sample_from(token_probs(logits, params), rng.random())


def speculative_verify(target_rows, draft_rows, proposals, params, rng):
    """Exact rejection sampling over one slot's K draft proposals.

    ``target_rows``/``draft_rows`` are (K, V) LOGITS: row ``j`` is the
    distribution for the stream position proposal ``j`` fills (target
    row ``j`` came out of the S=K verify dispatch, draft row ``j`` out
    of proposal dispatch ``j``). Returns ``(accepted, tokens)`` where
    ``accepted`` counts proposals kept and ``tokens`` is what the slot
    commits this iteration: the accepted prefix, plus — when a proposal
    was rejected — one token sampled from the residual
    ``max(p - q, 0)`` (so 1 <= len(tokens) <= K always, and every
    emitted token has nonzero target probability). Under greedy this
    degenerates to: accept while draft and target argmaxes agree, then
    emit the target argmax — bit-identical to target-only decode."""
    proposals = [int(d) for d in proposals]
    emitted = []
    for j, d in enumerate(proposals):
        p = token_probs(target_rows[j], params)
        q = token_probs(draft_rows[j], params)
        pd, qd = float(p[d]), float(q[d])
        if params.greedy:
            accept = pd > 0.0               # one-hot match, no draw
        elif qd <= 0.0:
            # the draft could not have proposed d with q(d)=0 unless
            # filters diverged; accept only if the target admits it
            accept = pd > 0.0
        else:
            accept = rng.random() < min(1.0, pd / qd)
        if accept:
            emitted.append(d)
            continue
        resid = np.maximum(p - q, 0.0)
        if resid.sum() <= 0.0:
            resid = p                        # degenerate: q covers p
        if params.greedy:
            emitted.append(int(np.argmax(resid)))
        else:
            emitted.append(sample_from(resid, rng.random()))
        return j, emitted
    return len(proposals), emitted
