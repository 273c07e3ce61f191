"""The OLMoE architecture (archs/olmoe.py, reference/olmoe.py, the
``moe.*`` metrics) rehearsed on the CPU at a tiny size: a tiny
configuration (tests/fixtures/olmoe/) and a cell in a temporary copy of
the rehearsal manifest, traced and untraced. By hand, not part of
tier-1 (two CPU rehearsals, a minute)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest  # noqa: E402

REHEARSAL = os.path.join(HERE, "rehearsal")
FIXTURE = os.path.join(HERE, "fixtures", "olmoe")
CELL = "tiny-olmoe-chat"
MOE_COUNTERS = ("serve.decode.moe.layer_steps", "serve.decode.moe.assignments",
                "serve.decode.moe.experts_touched",
                "serve.decode.moe.max_expert_load")
MOE_METRICS = ("moe.experts_touched_per_layer_step", "moe.load_imbalance",
               "moe.expert_share_of_step", "moe_expert_roofline")


def _add_tiny_olmoe(root):
    """The tiny configuration and its cell into the copy under ``root``:
    one new file and manifest entries; the architecture, its reference
    and the metrics' readers are the benchmark's own."""
    bench = root / "chipbench"
    dest = bench / "tests" / "rehearsal" / "configs" / "tiny-olmoe.json"
    assert not dest.exists()
    shutil.copy(os.path.join(FIXTURE, "configs", "tiny-olmoe.json"), dest)
    man = manifest.load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    inside = "chipbench/tests/rehearsal/"
    man["paths"] = [inside.rstrip("/")]
    for c in man["configs"]:
        c["file"] = inside + c["file"]
    man["configs"].append({
        "name": "tiny-olmoe", "source": "fixture", "reduced": [],
        "file": inside + "configs/tiny-olmoe.json", "why": "rehearsal"})
    man["workloads"].append({
        "name": CELL, "config": "tiny-olmoe", "traffic": "tiny-chat",
        "chips": 1, "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "serve_ttft_p90_ms",
                         "sched.tokens_per_iter", "sched.window_iter_share",
                         "engine.step_ms_p50", "decode_program_roofline"):
            m["workloads"].append(CELL)
    real = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in MOE_METRICS:        # as BENCHMARK.json declares them
        man["per_layer"].append(dict(real[name], workloads=[CELL]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))


def _files(root):
    return {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def copy_with_olmoe(tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):       # the system under test
        os.symlink(os.path.join(ROOT, program), root / program)
    before = _files(root)
    _add_tiny_olmoe(root)
    return root, before


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_olmoe_rehearses(copy_with_olmoe, trace):
    root, before = copy_with_olmoe
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(root / "chipbench" / "run.py"), "--rehearse",
         "--manifest", str(root / "BENCHMARK.json"),
         "--workload", CELL, "--seed", "3280000019",
         "--seconds", "2", "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    by = {l["chipbench"]: l for l in lines[:-1]}
    last = lines[-1]
    assert "chipbench" not in last          # the result is the last line
    assert by["reference"]["ok"], by["reference"]
    detail = by["reference_detail"]
    assert by["reference"]["tolerance"] == detail["tolerance"]   # its own
    assert 0.0 <= detail["routing_flip_share"] <= 0.2
    assert detail["control_max_abs_err"] > \
        detail["bfloat16_emulation_max_abs_err"]
    assert last["correct"] and last["attempted"] > 0 and not last["failed"]
    assert by["window"]["counters"]["serve.decode.tokens"] > 0
    if trace:
        counters = by["traced"]["counters"]
        for name in MOE_COUNTERS:
            assert counters[name] > 0, name
        # two layers an iteration; the window's edges fall inside one
        assert abs(counters["serve.decode.moe.layer_steps"]
                   - 2 * counters["serve.decode.iterations"]) <= 2
        touched = last["metrics"]["moe.experts_touched_per_layer_step"]
        assert 1.0 <= touched["value"] <= 8.0
        # the reader scales by the published 64 experts; here there are 8
        assert last["metrics"]["moe.load_imbalance"]["value"] >= 8.0
        # the CPU's trace has no XLA Ops line: the readers over the
        # device trace find nothing and the line leaves them out
        assert "moe_expert_roofline" not in last["metrics"]
        assert "moe.expert_share_of_step" not in last["metrics"]
    else:
        assert set(last["metrics"]) == {"serve_tokens_per_s",
                                        "serve_ttft_p90_ms", "setup_s"}
    after = _files(root)
    assert all(after[p] == data for p, data in before.items())
    added = sorted(str(p.relative_to(root)) for p in set(after) - set(before))
    assert added == ["chipbench/tests/rehearsal/configs/tiny-olmoe.json"]
