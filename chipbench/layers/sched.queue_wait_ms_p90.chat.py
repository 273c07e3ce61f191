"""Nearest-rank 90th percentile of the ring's serve.decode.queue.wait spans: submit to admission into a slot."""
from chipbench import spans


def read(obs):
    return spans.ring_span_percentile_ms(obs, "serve.decode.queue.wait", 90)
