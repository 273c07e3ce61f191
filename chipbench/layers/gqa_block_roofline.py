"""Least time to read the K and V rows MEASURED as attended in a block dispatch (ring: serve.decode.step records with the field block, attn_attended positions over slots and layers x one position's K and V bytes of one layer, the architecture's cost gqa_row, over the HBM peak) over the attention read kernel's (XLA Ops named decode_attn: 8 query heads x 4 rows of a K/V head) device time per run of the top rung's block program, in percent. Never clipped. A program with no block dispatch (every parent of PR 60) reports nothing."""
from chipbench import block_time, costs, kernel_time
from chipbench.stats import median


def read(obs):
    found = kernel_time.kernel_ms_in_module(
        obs.get("events") or [], block_time.top_rung_block_module(obs),
        "decode_attn")
    row = (obs.get("cost") or {}).get("gqa_row")
    rows = [r["attn_attended"] for r in block_time.block_records(obs)
            if "attn_attended" in r]
    if found is None or row is None or not rows:
        return None
    kernel_ms, _program_ms, runs = found
    peak = costs.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    least_ms = 1e3 * median(rows) * row["bytes"] / peak
    return 100.0 * least_ms / (kernel_ms / runs)
