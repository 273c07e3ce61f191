"""``tools/window_pack_check.py --rehearse``: the by-hand check of a
packed window program (a slot prefilling, the others riding with a token
each) runs end to end for every configuration it names, at the tiny
fixtures' sizes on the CPU - the script's paths and arguments, nothing of
a device. One process a configuration, as on the chip: the tool sets the
process's caches and the configuration's environment."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(ROOT, "tools", "window_pack_check.py")


def _configs():
    spec = importlib.util.spec_from_file_location("window_pack_check", TOOL)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return sorted(tool._TINY)


def test_the_tool_names_every_fed_configuration_of_the_benchmark():
    """Every serving configuration under ``chipbench/configs`` but
    EvaByte's (whose window is not a row per position: its by-hand check
    is ``chipbench/tests/evabyte_long.py``) and SDAR's (a window of a
    model that decodes by blocks carries prefill alone - nobody rides it
    with one token, which is what the tool feeds: ``check_reference``
    and ``tests/test_sdar_moe.py`` feed its windows whole blocks) can be
    asked for."""
    held = {os.path.splitext(f)[0]
            for f in os.listdir(os.path.join(ROOT, "chipbench", "configs"))}
    assert set(_configs()) == held - {"resnet50-imagenet", "evabyte-6.5b",
                                      "sdar-30b-a3b-chat"}


@pytest.mark.parametrize("config", _configs())
def test_window_pack_check_rehearses(config, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               MXNET_CRASH_DIR=str(tmp_path / "crash"))
    env.pop("MXNET_KERNEL_TIER", None)
    run = subprocess.run(
        [sys.executable, TOOL, "--config", config, "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["platform"] == "cpu" and line["ok"]
    slots, S = line["slots"], line["window"]
    assert line["fed"] == [S] + [1] * (slots - 1)
    assert S + slots - 1 <= line["budget_rows"] < slots * S
    assert line["head_rows"] == slots
    # the riders' rows of the last window and slot 0's of every window,
    # packed against whole; what either form wrote
    assert line["riders_argmax_equal"] == slots - 1
    assert line["riders_packed_vs_whole_in_tol_units"] < 1
    assert line["packed_vs_whole_in_tol_units"] < 1
    assert line["rows_written_equal"]
    assert line["packed_vs_reference"]["max_err_over_bound"] <= 1
