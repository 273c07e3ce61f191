"""Per traced S=1 iteration, the end of serve.decode.iter.fetch to the next decode.step.stage's start: commit, rewind, account, the loop, the lock, plan; the median, in ms."""
from chipbench import critical_path


def read(obs):
    return critical_path.segment_ms_p50(obs, "turnaround")
