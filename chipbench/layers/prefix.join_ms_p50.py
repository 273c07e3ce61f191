"""Median of the ring's serve.decode.prefix.join spans, in ms: from before a joined slot's stored rows are put to the chip and written into its pools to after its cursor is set - what a request that joins at a resident document waits for in place of the document's prefill."""
from chipbench import spans


def read(obs):
    return spans.ring_span_percentile_ms(obs, "serve.decode.prefix.join", 50)
