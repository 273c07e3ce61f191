"""Median io.prefetch.to_device: the producer thread staging one batch onto the device."""
from chipbench import spans


def read(obs):
    return spans.median_ms(obs, "io.prefetch.to_device")
