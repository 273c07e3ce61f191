"""Per traced S=1 iteration, the end of the dispatch's last device op to the end of serve.decode.iter.fetch.ids (the ids on the host); the median, in ms."""
from chipbench import critical_path


def read(obs):
    return critical_path.segment_ms_p50(obs, "wake_latency")
