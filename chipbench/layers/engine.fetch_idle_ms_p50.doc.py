"""Per serve.decode.iter.fetch event, the ms in which no device operation ran; the median."""
from chipbench import spans


def read(obs):
    return spans.idle_ms_p50(obs, "serve.decode.iter.fetch")
