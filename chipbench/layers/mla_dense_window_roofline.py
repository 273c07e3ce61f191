"""Least time for the latent attention that the equations ask of a window dispatch, over the device time of the XLA Ops named mla_* per run of the top rung's window program, in percent. Never clipped. The work is MEASURED, not assumed: the ring's serve.decode.step records with window > 1 carry mla_pairs, the (query, key) pairs of the real queries over slots and layers, and mla_attended, the keys at or before each fed slot's last query; a record's least FLOPs are the cheaper of the absorbed form (pairs x mla_pair_absorbed) and the expanded one (pairs x mla_pair_expanded + keys x mla_key_expansion), its least bytes the keys' latent rows; the median record's max(FLOPs / peak, bytes / bandwidth). Pads and a riding slot's unused query rows are no work: a kernel that computes them reads a lower share. Counting the architecture's mla_window instead (every slot fed all 1,024 rows, in the cheaper form) read 95 % on the first traced run for a kernel that computes three times the real pairs (PERF.md, section 6, PR 40)."""
import re

from chipbench import costs, kernel_time, trace
from chipbench.stats import median

_WINDOW_PROGRAM = re.compile(r"fwd_infer_(\d+)x(\d+)$")


def _top_rung_window_module(events):
    best = None
    for name in trace.modules(events):
        m = _WINDOW_PROGRAM.search(name.split("(")[0])
        if m and int(m.group(2)) > 1:
            key = (int(m.group(1)), int(m.group(2)))
            if best is None or key > best[0]:
                best = (key, name)
    return None if best is None else best[1]


def read(obs):
    events = obs.get("events") or []
    cost = obs.get("cost") or {}
    units = [cost.get(k) for k in ("mla_pair_absorbed", "mla_pair_expanded",
                                   "mla_key_expansion")]
    found = kernel_time.kernel_ms_in_module(
        events, _top_rung_window_module(events), "mla_")
    recs = [r for r in obs.get("ring") or []
            if r.get("kind") == "serve.decode.step"
            and r.get("window", 1) > 1 and "mla_pairs" in r]
    if found is None or None in units or not recs:
        return None
    absorbed, expanded, expansion = units
    rung = max(r.get("rung", 0) for r in recs)
    pk = costs.peaks(obs["device_kind"])
    least_s = median([max(
        min(r["mla_pairs"] * absorbed["flops"],
            r["mla_pairs"] * expanded["flops"]
            + r["mla_attended"] * expansion["flops"])
        / pk["bf16_flops_per_s"],
        r["mla_attended"] * expansion["bytes"] / pk["hbm_bytes_per_s"])
        for r in recs if r.get("rung", 0) == rung])
    return 100.0 * least_s * 1e3 / (found[0] / found[2])
