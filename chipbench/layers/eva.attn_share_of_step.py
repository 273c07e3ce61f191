"""% of the top rung's S=1 program's device time spent in the EVA kernels (XLA Ops named eva_*: eva_summarise, eva_attn_decode, eva_write)."""
from chipbench import kernel_time


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events), "eva_")
    if found is None:
        return None
    kernel_ms, program_ms, _runs = found
    return 100.0 * kernel_ms / program_ms
