"""Least time for the experts a window dispatch touched over the device time of the grouped expert kernels (XLA Ops named moe_gmm*: moe_gmm_gate_up and moe_gmm_down, inside the held experts' loop over segments of the sorted assignments) per run of the top rung's longest window program, in percent. The least, from the median ring record (serve.decode.step, window > 1, top rung): moe_touched (held experts with at least one assignment, summed over the layers) x the cost moe_expert's bytes over the HBM peak, or moe_held (the assignments that landed on held experts, summed over the layers) x the cost moe_assignment's FLOPs over the MXU peak, the larger. An expert whose rows straddle two row tiles or two segments is read twice and counted once: that lowers the share and cannot raise it. The sort, the gather of the rows and the weighted scatter back (XLA fusions inside the loop) have no name of their own: their time is not in the denominator. Never clipped."""
from chipbench import costs, kernel_time
from chipbench.layers.ssm_window_roofline import _top_rung_window_module
from chipbench.stats import median

_FIELDS = ("moe_touched", "moe_held")


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, _top_rung_window_module(events), "moe_gmm")
    cost = obs.get("cost") or {}
    expert, one = cost.get("moe_expert"), cost.get("moe_assignment")
    recs = [r for r in obs.get("ring") or []
            if r.get("kind") == "serve.decode.step"
            and r.get("window", 1) > 1 and all(f in r for f in _FIELDS)]
    if found is None or expert is None or one is None or not recs:
        return None
    rung = max(r.get("rung", 0) for r in recs)
    recs = [r for r in recs if r.get("rung", 0) == rung]
    touched, held = (median([r[f] for r in recs]) for f in _FIELDS)
    least_s, _bound = costs.roofline(
        {"flops": held * one["flops"], "bytes": touched * expert["bytes"]},
        obs["device_kind"])
    return 100.0 * 1e3 * least_s / (found[0] / found[2])
