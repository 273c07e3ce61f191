"""Least time to score the index keys MEASURED as scored in an S=1 dispatch (ring: serve.decode.step records with window 1, dsa_scored keys over slots and full layers: the larger of their bytes over the HBM peak and their score FLOPs over the compute peak) over the selection kernels' (XLA Ops named dsa_index_scores and dsa_topk) device time per S=1 dispatch, in percent. Never clipped."""
from chipbench import costs, kernel_time
from chipbench.stats import median


def read(obs):
    events = obs.get("events") or []
    module = kernel_time.top_rung_decode_module(events)
    found = [kernel_time.kernel_ms_in_module(events, module, name)
             for name in ("dsa_index_scores", "dsa_topk")]
    key = (obs.get("cost") or {}).get("dsa_key")
    keys = [r["dsa_scored"] for r in obs.get("ring") or []
            if r.get("kind") == "serve.decode.step"
            and r.get("window") == 1 and "dsa_scored" in r]
    if None in found or key is None or not keys:
        return None
    scored = median(keys)
    least_s, _bound = costs.roofline(
        {"flops": scored * key["flops"], "bytes": scored * key["bytes"]},
        obs["device_kind"], obs.get("chips", 1))
    kernel_ms = sum(f[0] for f in found) / found[0][2]
    return 100.0 * least_s * 1e3 / kernel_ms
