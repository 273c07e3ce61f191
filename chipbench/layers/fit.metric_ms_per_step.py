"""Median module.fit.update_metric: the fit loop's per-step metric update (reads the step's outputs)."""
from chipbench import spans


def read(obs):
    return spans.median_ms(obs, "module.fit.update_metric")
