"""Per traced S=1 iteration, decode.step.launch's start to the first XLA Op of that iteration's step program; the median, in ms."""
from chipbench import critical_path


def read(obs):
    return critical_path.segment_ms_p50(obs, "launch_latency")
