"""Least time to read the experts MEASURED as touched in a block dispatch (ring: serve.decode.step records with the field block, moe_touched x one expert's bytes, over the HBM peak) over the grouped expert kernels' (XLA Ops named moe_gmm*) device time per run of the top rung's block program, in percent. Never clipped. A program with no block dispatch (every parent of PR 60) reports nothing."""
from chipbench import block_time, costs, kernel_time
from chipbench.stats import median


def read(obs):
    found = kernel_time.kernel_ms_in_module(
        obs.get("events") or [], block_time.top_rung_block_module(obs),
        "moe_gmm")
    expert = (obs.get("cost") or {}).get("moe_expert")
    touched = [r["moe_touched"] for r in block_time.block_records(obs)
               if "moe_touched" in r]
    if found is None or expert is None or not touched:
        return None
    kernel_ms, _program_ms, runs = found
    peak = costs.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    least_ms = 1e3 * median(touched) * expert["bytes"] / peak
    return 100.0 * least_ms / (kernel_ms / runs)
