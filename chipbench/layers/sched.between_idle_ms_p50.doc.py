"""Per serve.decode.iter event, device-idle ms from its start to the next one's, outside its dispatch and fetch children; the median."""
from chipbench import spans


def read(obs):
    return spans.idle_between_ms_p50(
        obs, "serve.decode.iter",
        ("serve.decode.iter.dispatch", "serve.decode.iter.fetch"))
