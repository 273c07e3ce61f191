"""Median io.prefetch.batch: one queue entry of PrefetchingIter's producer thread, fetch + staging + put."""
from chipbench import spans


def read(obs):
    return spans.median_ms(obs, "io.prefetch.batch")
