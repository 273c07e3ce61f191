"""The benchmark's own tests of the SDAR architecture
(chipbench/tests/test_sdar_moe.py: the interface with both optional
names, the configuration against the catalog, the costs by hand, every
new reader on a synthetic obs, the traffic file against the issue's
eight pairs, ``make_params`` and the controls, and the cell's CPU
rehearsal at a tiny size) run in tier-1 as they stand - but for the
rehearsal, whose copy there pins PR 60's order of an iteration
(``serve.decode.runahead.launched == 0``: nothing ran ahead of a block
engine) and is the next ``benchmark`` PR's to change (PERF.md section 7,
C15): the one below is that test line for line, with that one assertion
replaced by the order since PR 61; the block against its reference is
``tests/test_sdar_moe.py``'s, in a file of its own so that the two run
on two workers."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.tests import test_sdar_moe as _bench  # noqa: E402
from chipbench.tests.test_sdar_moe import (  # noqa: E402,F401
    copy_with_sdar,
    test_costs_against_a_count_by_hand,
    test_every_new_reader_on_a_synthetic_obs,
    test_make_params_is_seeded_and_the_controls_are_switches,
    test_the_architecture_file_has_the_interface_with_both_optional_names,
    test_the_configuration_is_the_catalogs_but_for_what_reduced_lists,
    test_the_traffic_is_the_issues)


def test_tiny_sdar_rehearses(copy_with_sdar):
    """``chipbench/tests/test_sdar_moe.py::test_tiny_sdar_rehearses``
    line for line, but for ``runahead.launched == 0``: the block
    dispatches are launched ahead, each committed one counted."""
    root = copy_with_sdar
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(root / "chipbench" / "run.py"), "--rehearse",
         "--manifest", str(root / "BENCHMARK.json"),
         "--workload", _bench.CELL, "--seed", "3280000019",
         "--seconds", "2", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.startswith("{")]
    by = {line["chipbench"]: line for line in lines[:-1]}
    last = lines[-1]
    assert "chipbench" not in last          # the result is the last line
    reference = by["reference"]
    assert last["correct"] and reference["ok"] is True
    assert reference["decode_step_len"] == 4 and reference["masked_feeds"] == 4
    assert reference["masked_max_err_over_bound"] < 1
    assert reference["fed_windows"]["packed"] == [True, True]
    assert reference["fed_windows"]["max_err_over_bound"] < 1
    assert reference["tokens"] == 80
    detail = by["reference_detail"]
    assert reference["tolerance"] == detail["tolerance"]        # its own
    assert detail["positions_compared"] == 32
    for key in ("fp8", "causal", "left_behind"):
        assert detail[f"{key}_control_max_abs_err"] > 0
    assert 0.0 <= detail["routing_flip_share"] < 1.0
    assert last["attempted"] > 0 and not last["failed"]
    assert by["window"]["compiles_in_window"] == []
    assert last["compared"]["masked_err_over_bound"]["value"] < 1
    counters = by["traced"]["counters"]
    assert counters["serve.decode.diffusion.blocks"] > 0
    # since PR 61: every block dispatch but the one behind a window runs
    # ahead (the tiny mix's answers are two or three blocks: half its
    # dispatches are windows or the block dispatch behind one), counted
    # at its commit, an iteration behind its launch, so the two
    # counters' growth over a window differs by one at most
    ahead = counters["serve.decode.runahead.blocks"]
    assert counters["serve.decode.iterations"] / 3 < ahead
    assert abs(ahead - counters["serve.decode.runahead.launched"]) <= 1
    assert counters.get("serve.decode.runahead.dropped", 0) == 0
    # five feeds a block but for the first blocks, which hold prompt
    # tokens (every prompt of the tiny mix is off a block's edge)
    metrics = last["metrics"]
    assert 4.0 < metrics["diffusion.feeds_per_block"]["value"] <= 5.0
    assert 70 < metrics["diffusion.dropped_share_of_rows"]["value"] <= 80
    assert 0 < metrics["sched.block_iter_share"]["value"] < 100
    # on the CPU there is no device trace: the three rooflines read
    # nothing and are left out; every counter and ring metric is there
    want = set(_bench.NEW_METRICS + _bench.SHARED_METRICS) - {
        "block_step_roofline", "moe_block_roofline", "gqa_block_roofline"}
    assert set(metrics) == want
