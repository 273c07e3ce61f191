"""One general traffic generator, driven by a data file.

A traffic mix is ``traffic/<name>.json``. Serving mixes:

  kind          "closed_loop" (``clients`` callers, each sends its next
                request when the last one completes) or "open_loop"
                (arrivals on a schedule, whatever the server does)
  block         a list of (prompt_tokens, answer_tokens) pairs. The
                generator deals WHOLE blocks, in the file's order: every
                seed offers the same requests in the same order, and the
                seed sets only the token ids (and the weights)
  shuffle_blocks  optional, default false: the seed also permutes the
                order inside each block. On the chip the order alone
                moved tokens/s by 6 % (which prefills share a window
                iteration), so the cells keep the file's order
  rate_rps      open loop: mean arrivals per second
  arrival       open loop: "poisson" (default) or "uniform"
  burst         open loop, optional: {"every_s": .., "size": ..} adds
                ``size`` simultaneous arrivals every ``every_s`` seconds
  prefix        optional: {"count": n, "len": L} - the first L prompt
                tokens come from one of n shared prefixes, submitted
                with ``prefix_id`` (prompts shorter than L are unshared).
                The prefixes are dealt in turn, whatever the seed: the
                i-th sharing request of the run opens with prefix
                i mod n. (Until PR 59 the seed drew each; which prompts
                shared a document, and which prompt's rows the store
                kept, then moved the A.X-K1 cell's TTFT p90 by 16 %
                from seed to seed where one seed repeats to 0.3 %, and
                1 seed in 40 left a document out of the lead-in.)
  lead_in_blocks  blocks completed before the window opens

The seed is any whole number; it is folded to 32 bits for numpy.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Request:
    index: int
    block: int
    prompt_len: int
    max_new: int
    prompt: np.ndarray
    prefix_id: str | None = None
    due_s: float | None = None      # open loop: offset from the start


def _rng(seed, *salt):
    return np.random.default_rng([int(seed) % (1 << 32), *salt])


def requests(traffic, vocab_size, seed):
    """An endless iterator of ``Request``: block after block, in the
    file's order (or, with ``shuffle_blocks``, each a seeded
    permutation)."""
    block = [tuple(p) for p in traffic["block"]]
    prefix = traffic.get("prefix")
    prefixes = None
    if prefix:
        prng = _rng(seed, 7)
        prefixes = [prng.integers(0, vocab_size, prefix["len"])
                    for _ in range(prefix["count"])]
    index = 0
    sharing = 0
    b = 0
    while True:
        rng = _rng(seed, 1, b)
        order = rng.permutation(len(block)) \
            if traffic.get("shuffle_blocks") else range(len(block))
        for j in order:
            prompt_len, max_new = block[j]
            prompt = rng.integers(0, vocab_size, prompt_len)
            prefix_id = None
            if prefixes is not None and prompt_len > prefix["len"]:
                k = sharing % len(prefixes)
                sharing += 1
                prompt[:prefix["len"]] = prefixes[k]
                prefix_id = f"prefix-{k}"
            yield Request(index, b, int(prompt_len), int(max_new),
                          prompt.astype(np.int32), prefix_id)
            index += 1
        b += 1


def arrivals(traffic, seed, horizon_s):
    """Open loop: sorted arrival offsets in [0, horizon_s)."""
    rate = float(traffic["rate_rps"])
    rng = _rng(seed, 3)
    if traffic.get("arrival", "poisson") == "uniform":
        times = list(np.arange(0.0, horizon_s, 1.0 / rate))
    else:
        n = int(rate * horizon_s * 1.5) + 16
        times = np.cumsum(rng.exponential(1.0 / rate, n))
        times = [float(t) for t in times if t < horizon_s]
    burst = traffic.get("burst")
    if burst:
        t = float(burst["every_s"])
        while t < horizon_s:
            times += [t] * int(burst["size"])
            t += float(burst["every_s"])
    return sorted(times)


def block_totals(traffic):
    """(requests, prompt tokens, answer tokens) of one block."""
    block = traffic["block"]
    return (len(block), sum(p for p, _ in block), sum(a for _, a in block))
