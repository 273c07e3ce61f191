"""The CPU rehearsal: every runner end to end at the tiny sizes of
``chipbench/tests/rehearsal``, the plain references against the program,
and the refusal to run without the chip. Slow (a minute or two); by
hand, not part of tier-1. No number these print is a device number."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK.json")


def _run(workload, trace, devices=1, extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", workload, "--seed", "3000000019", "--seconds", "2",
         "--trace", str(trace), *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    return proc, lines


@pytest.mark.parametrize("workload,devices,trace", [
    ("tiny-chat", 1, 0), ("tiny-chat", 1, 1), ("tiny-open", 1, 0),
    ("tiny-fit-1", 1, 1), ("tiny-fit-4", 4, 0)])
def test_rehearsal_runs_and_agrees_with_the_plain_reference(
        workload, devices, trace):
    proc, lines = _run(workload, trace, devices,
                       ("--rehearse", "--manifest", MANIFEST))
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = lines[-1]
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["device"]["platform"] == "cpu"       # and says so
    assert last["attempted"] > 0 and last["failed"] == 0
    by = {l["chipbench"]: l for l in lines[:-1]}
    assert by["start"]["rehearsal"] and by["window"]["rehearsal"]
    if workload != "tiny-fit-4":    # batch 4 a device: BatchNorm is noisy
        assert by["reference"]["ok"], by["reference"]
    names = set(last["metrics"])
    if trace:
        assert "setup_s" not in names and names
    else:
        assert "setup_s" in names and len(names) >= 2
    for m in last["metrics"].values():
        assert m["value"] > 0


def test_no_chip_no_result():
    proc, lines = _run("resnet50-fit-1chip", 0)
    assert proc.returncode != 0
    assert "jax.devices() found" in proc.stderr
    assert not any("metrics" in l for l in lines)
