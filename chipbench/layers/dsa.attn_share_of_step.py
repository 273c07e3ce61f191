"""% of the top rung's S=1 program's device time spent in the latent-attention and selection kernels (XLA Ops named mla_* and dsa_*: mla_write, mla_attn_decode, dsa_write, dsa_index_scores, dsa_topk)."""
from chipbench import kernel_time


def read(obs):
    events = obs.get("events") or []
    module = kernel_time.top_rung_decode_module(events)
    found = [kernel_time.kernel_ms_in_module(events, module, prefix)
             for prefix in ("mla_", "dsa_")]
    if None in found:
        return None
    return 100.0 * sum(f[0] for f in found) / found[0][1]
