"""Least time for one block step's work over the measured device time of the top rung's block program (XLA Modules fwd_infer_<slots>x<L>, by name, L the ring's block), in percent: the share of the whole step. The work: the architecture's cost block_step_fixed (every weight outside the experts once, the K and V rows attended, the rows written, float32 logits out, and every operation) plus the experts MEASURED as touched (ring: median moe_touched of the block dispatches x one expert's bytes, cost moe_expert) - max(FLOPs / peak, bytes / bandwidth); at 32 rows the bytes. Measured and not the even-routing expectation of cost block_step: a block's undecided positions are all the mask id and route alike. Never clipped. A program with no block dispatch (every parent of PR 60) reports nothing."""
from chipbench import block_time, costs, trace
from chipbench.stats import median


def read(obs):
    name = block_time.top_rung_block_module(obs)
    cost = obs.get("cost") or {}
    fixed, expert = cost.get("block_step_fixed"), cost.get("moe_expert")
    touched = [r["moe_touched"] for r in block_time.block_records(obs)
               if "moe_touched" in r]
    if name is None or fixed is None or expert is None or not touched:
        return None
    ms = trace.module_ms(obs["events"], name)
    least_s, _bound = costs.roofline(
        {"flops": fixed["flops"],
         "bytes": fixed["bytes"] + median(touched) * expert["bytes"]},
        obs["device_kind"], obs.get("chips", 1))
    return 100.0 * least_s * 1e3 / ms
