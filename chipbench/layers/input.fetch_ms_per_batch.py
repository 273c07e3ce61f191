"""Median io.prefetch.fetch: the producer thread inside the inner iterators' next()."""
from chipbench import spans


def read(obs):
    return spans.median_ms(obs, "io.prefetch.fetch")
