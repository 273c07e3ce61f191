"""% of the top rung's S=1 program's device time spent in the latent-attention kernels (XLA Ops named mla_*: mla_write, mla_attn_decode) of a graph whose attention takes no selection."""
from chipbench import kernel_time


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events), "mla_")
    if found is None:
        return None
    return 100.0 * found[0] / found[1]
