"""% of the top rung's S=1 program's device time spent in the attention read (XLA Ops named decode_attn)."""
from chipbench import kernel_time


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events), "decode_attn")
    if found is None:
        return None
    kernel_ms, program_ms, _runs = found
    return 100.0 * kernel_ms / program_ms
