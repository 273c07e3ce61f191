"""Rows a weight read serves: the assignments that landed on held experts over the held experts that got at least one, summed over the layers of an S=1 dispatch (ring: serve.decode.step records with window 1, moe_held / moe_touched; the median record). A grouped matmul reads a touched expert's matrices once whatever its rows, so this is the arithmetic a byte of expert weight carries in a decode step; a deployment's chip, which runs its experts for two chips' tokens, reads about twice the rows an expert."""
from chipbench.stats import median


def read(obs):
    vals = [r["moe_held"] / r["moe_touched"] for r in obs.get("ring") or []
            if r.get("kind") == "serve.decode.step" and r.get("window") == 1
            and r.get("moe_touched") and "moe_held" in r]
    return median(vals) if vals else None
