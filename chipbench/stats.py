"""The arithmetic of the end-to-end metrics. Pure functions of
timestamps, so that a scripted timeline checks them."""
from __future__ import annotations

import math


def percentile(values, q):
    """Nearest-rank percentile (q in 0..100) of ``values``; an infinite
    sample (a failed or unfinished request) sorts last. None for no
    samples."""
    vals = sorted(values)
    if not vals:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def median(values):
    vals = sorted(values)
    if not vals:
        return None
    n = len(vals)
    return vals[n // 2] if n % 2 else 0.5 * (vals[n // 2 - 1] + vals[n // 2])


def in_window(stamps, t_open, t_close):
    return [t for t in stamps if t_open <= t < t_close]


def tokens_per_s(token_stamps, t_open, t_close):
    """Output tokens whose emission timestamp falls in
    [t_open, t_close), over the window: credited token by token, never
    per completed request. ``token_stamps`` is one list per request."""
    n = sum(len(in_window(s, t_open, t_close)) for s in token_stamps)
    return n / (t_close - t_open)


def token_gaps(token_stamps, t_open, t_close):
    """Gaps between consecutive output tokens of one request, for every
    token emitted inside the window (its predecessor may lie before
    it)."""
    gaps = []
    for stamps in token_stamps:
        for a, b in zip(stamps, stamps[1:]):
            if t_open <= b < t_close:
                gaps.append(b - a)
    return gaps


def ttfts(requests, t_open, t_close):
    """Submit -> first token, for requests submitted inside the window.
    ``requests`` holds ``(t_submit, t_first_or_None, failed)``; a failed
    or unfinished request counts as the largest (inf)."""
    out = []
    for t_submit, t_first, failed in requests:
        if not t_open <= t_submit < t_close:
            continue
        out.append(math.inf if failed or t_first is None
                   else t_first - t_submit)
    return out
