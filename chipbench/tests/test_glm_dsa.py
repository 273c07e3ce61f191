"""The GLM-5.2 architecture (archs/glm_dsa.py, reference/glm_dsa.py, the
``dsa.*`` / ``mla_*`` metrics and ``moe.held_assignment_share``)
rehearsed on the CPU at a tiny size: a tiny configuration and traffic
mix (tests/fixtures/glm_dsa/) and a cell in a temporary copy of the
rehearsal manifest, traced and untraced; the architecture's costs
against a count by hand at one shape; both controls against the
reference. By hand, not part of tier-1 (two CPU rehearsals, a few
minutes). On the chip at the published widths: ``glm_long.py``."""
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
FIXTURE = os.path.join(HERE, "fixtures", "glm_dsa")
CELL = "tiny-glm-longctx"
COUNTERS = ("serve.decode.dsa.layer_steps", "serve.decode.dsa.live_rows",
            "serve.decode.dsa.selected_rows", "serve.decode.dsa.scored_rows",
            "serve.decode.moe.held_assignments")
METRICS = ("dsa.selected_share_of_keys", "moe.held_assignment_share",
           "dsa.attn_share_of_step", "mla_decode_roofline",
           "dsa_index_roofline", "mla_window_roofline")


def _add_tiny_glm(root):
    """The tiny configuration, its traffic mix and its cell into the
    copy under ``root``: two new files and manifest entries; the
    architecture, its reference and the metrics' readers are the
    benchmark's own."""
    inside = "chipbench/tests/rehearsal/"
    for kind, name in (("configs", "tiny-glm.json"),
                       ("traffic", "tiny-longctx.json")):
        dest = root / inside / kind / name
        assert not dest.exists()
        shutil.copy(os.path.join(FIXTURE, kind, name), dest)
    man = manifest.load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    man["paths"] = [inside.rstrip("/")]
    for c in man["configs"]:
        c["file"] = inside + c["file"]
    man["configs"].append({
        "name": "tiny-glm", "source": "fixture", "reduced": [],
        "file": inside + "configs/tiny-glm.json", "why": "rehearsal"})
    man["workloads"].append({
        "name": CELL, "config": "tiny-glm", "traffic": "tiny-longctx",
        "chips": 1, "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "serve_ttft_p90_ms",
                         "sched.tokens_per_iter", "sched.window_iter_share",
                         "engine.step_ms_p50", "decode_program_roofline"):
            m["workloads"].append(CELL)
    real = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in METRICS:            # as BENCHMARK.json declares them
        man["per_layer"].append(dict(real[name], workloads=[CELL]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))


def _files(root):
    return {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def copy_with_glm(tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):       # the system under test
        os.symlink(os.path.join(ROOT, program), root / program)
    before = _files(root)
    _add_tiny_glm(root)
    return root, before


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_glm_rehearses(copy_with_glm, trace):
    root, before = copy_with_glm
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(root / "chipbench" / "run.py"), "--rehearse",
         "--manifest", str(root / "BENCHMARK.json"),
         "--workload", CELL, "--seed", "3280000019",
         "--seconds", "2", "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    by = {l["chipbench"]: l for l in lines[:-1]}
    last = lines[-1]
    assert "chipbench" not in last          # the result is the last line
    # prefill 4 x 16 and 16 steps: the comparison runs at positions
    # 48-79, where a query attends 16 of its 49-80 keys. At this size a
    # key swapped by bfloat16's rounding is a sixteenth of a query's
    # attention, so ``ok`` is the chip's to decide; here the lines exist
    # and the controls are further from the reference than the served
    # path's emulation
    assert by["reference"]["tokens"] == 80
    detail = by["reference_detail"]
    assert by["reference"]["tolerance"] == detail["tolerance"]   # its own
    assert detail["positions_compared"] == 32
    assert 0.0 <= detail["set_flip_share"] <= 1.0
    assert detail["control_max_abs_err"] > 0
    assert detail["selection_control_max_abs_err"] > 0
    assert last["attempted"] > 0 and not last["failed"]
    assert by["window"]["counters"]["serve.decode.tokens"] > 0
    if trace:
        counters = by["traced"]["counters"]
        for name in COUNTERS:
            assert counters[name] > 0, name
        assert counters["serve.decode.dsa.selected_rows"] \
            < counters["serve.decode.dsa.live_rows"]
        assert 30.0 < last["metrics"]["dsa.selected_share_of_keys"][
            "value"] < 100.0
        # 4 of 16 experts held: a quarter of the assignments, roughly
        assert 10.0 < last["metrics"]["moe.held_assignment_share"][
            "value"] < 45.0
        # the CPU's trace has no XLA Ops line: the readers over the
        # device trace find nothing and the line leaves them out
        for name in METRICS[2:]:
            assert name not in last["metrics"]
    after = _files(root)
    assert all(after[p] == data for p, data in before.items())
    added = sorted(str(p.relative_to(root)) for p in set(after) - set(before))
    assert added == ["chipbench/tests/rehearsal/configs/tiny-glm.json",
                     "chipbench/tests/rehearsal/traffic/tiny-longctx.json"]


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "glm-5.2.json")) as f:
        return json.load(f)


def _arch():
    return manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", "glm_dsa.py"))


def test_costs_against_a_count_by_hand():
    arch, cfg = _arch(), _published()
    assert arch.latent_row_bytes(cfg) == 1152
    assert arch.index_key_bytes(cfg) == 256
    assert arch.moe_expert_bytes(cfg) == 3 * 6144 * 2048 * 2
    got = arch.costs(cfg, 8, 1024, 8000.0)
    assert set(got) == {"decode_step", "window_step", "mla_window",
                        "mla_row", "dsa_key", "moe_expert"}
    mla = 6144 * 2048 + 2048 * 16384 + 6144 * 576 + 512 * 64 * 448 \
        + 16384 * 6144
    indexer = 2048 * 4096 + 6144 * (128 + 32)
    assert round(mla / 1e6, 1) == 165.0 and round(indexer / 1e6, 1) == 9.4
    outside = 5 * mla + 2 * indexer + 3 * 6144 * 12288 \
        + 4 * (3 * 6144 * 2048 + 6144 * 256) + 19360 * 6144
    # S = 1: 8 tokens' choices touch 16 * (1 - (31/32)**8) = 3.6 held
    # experts a layer; each slot's query attends 2,048 of 8,000 rows and
    # scores 8,000 index keys on the two full layers
    touched = 16 * (1 - (1 - 8 / 256) ** 8)
    state = 2 * 8 * 8000.5 * 256 + 5 * 8 * 2048 * 1152
    moved = 5 * 8 * 64 * 512 * 2 + 8 * (5 * 1152 + 2 * 256)
    want = outside * 2 + 4 * touched * 75497472 + 8 * 6144 * 2 + state \
        + moved + 8 * 19360 * 4
    assert got["decode_step"]["bytes"] == pytest.approx(want, rel=1e-12)
    # the window: 8,192 rows, 22 TFLOP of dense matmuls; the attention
    # and selection kernels' least work at 8,000 live rows
    assert 22e12 < got["window_step"]["flops"] < 30e12
    assert got["mla_window"]["flops"] == pytest.approx(
        2 * 8192 * 8512 * 8192 + 5 * 8192 * 2048 * 2 * 64 * 512, rel=1e-12)


def test_both_controls_are_further_than_the_emulation():
    """At a tiny size on the CPU, float32 parameters: the reference
    with every matmul operand in float8, and the reference without the
    selection, are each far from the reference; its bfloat16 emulation
    is near at the median position (a swapped key is a sixteenth of a
    query's attention here, so the worst position is not rounding)."""
    import jax.numpy as jnp
    import numpy as np
    arch = _arch()
    with open(os.path.join(FIXTURE, "configs", "tiny-glm.json")) as f:
        cfg = json.load(f)
    os.environ.setdefault("MXNET_KERNEL_TIER", "xla")
    symbol = arch.decode_symbol(cfg, 1)
    params = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(str(a.dtype) == "bfloat16" for a in params.values())
    assert np.asarray(params["lm_l0_ln1_gamma"], np.float32).all()
    assert params["lm_l1_moe_gate_weight"].shape == (4, 64, 32)
    assert params["lm_l1_moe_router_weight"].shape == (16, 64)
    for name in params:            # weights of real size: see evabyte
        if name.endswith("_weight") and "norm" not in name:
            params[name] = (np.asarray(params[name], np.float32) * 10) \
                .astype(params[name].dtype)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 80)).astype("i4")
    from chipbench.reference import glm_dsa as ref
    rcfg = dict(cfg, indexer_types=cfg["indexer_types_run"])
    want = np.asarray(ref.forward(params, tokens, rcfg))
    per_position = lambda x: np.median(              # noqa: E731
        np.max(np.abs(x - want[:, -32:]), axis=-1))
    fp8 = per_position(np.asarray(ref.forward(
        params, tokens, rcfg, round_to=jnp.float8_e4m3fn, tail=32)))
    dense = per_position(np.asarray(ref.forward(
        params, tokens, rcfg, select=False, tail=32)))
    emu = per_position(np.asarray(ref.forward(
        params, tokens, rcfg, round_to=jnp.bfloat16, tail=32)))
    assert emu < fp8 / 4 and emu < dense / 4, (emu, fp8, dense)
