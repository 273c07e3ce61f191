"""The Trinity architecture (archs/afmoe.py, reference/afmoe.py, the
``swa.*`` / ``gqa_*`` metrics) rehearsed on the CPU at a tiny size: a
tiny configuration and traffic mix (tests/fixtures/afmoe/) and a cell
in a temporary copy of the rehearsal manifest, traced and untraced; the
architecture's costs against a count by hand at the published shape;
the configuration against the catalog's row; the three controls against
the reference. The rehearsals are by hand, not part of tier-1 (two CPU
runs, a few minutes); the quick cases run in tier-1 through
``tests/test_chipbench_archs.py``."""
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
FIXTURE = os.path.join(HERE, "fixtures", "afmoe")
CELL = "tiny-afmoe-mixedctx"
COUNTERS = ("serve.decode.attn.live_rows", "serve.decode.attn.attended_rows",
            "serve.decode.attn.capacity_rows",
            "serve.decode.moe.layer_steps")
METRICS = ("swa.attended_share_of_keys", "gqa_decode_roofline",
           "gqa_window_roofline")


def _add_tiny_afmoe(root):
    """The tiny configuration, its traffic mix and its cell into the
    copy under ``root``: two new files and manifest entries; the
    architecture, its reference and the metrics' readers are the
    benchmark's own."""
    inside = "chipbench/tests/rehearsal/"
    for kind, name in (("configs", "tiny-afmoe.json"),
                       ("traffic", "tiny-mixedctx.json")):
        dest = root / inside / kind / name
        assert not dest.exists()
        shutil.copy(os.path.join(FIXTURE, kind, name), dest)
    man = manifest.load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    man["paths"] = [inside.rstrip("/")]
    for c in man["configs"]:
        c["file"] = inside + c["file"]
    man["configs"].append({
        "name": "tiny-afmoe", "source": "fixture", "reduced": [],
        "file": inside + "configs/tiny-afmoe.json", "why": "rehearsal"})
    man["workloads"].append({
        "name": CELL, "config": "tiny-afmoe", "traffic": "tiny-mixedctx",
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
def copy_with_afmoe(tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):       # the system under test
        os.symlink(os.path.join(ROOT, program), root / program)
    before = _files(root)
    _add_tiny_afmoe(root)
    return root, before


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_afmoe_rehearses(copy_with_afmoe, trace):
    root, before = copy_with_afmoe
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
    # 48-79, where a sliding layer attends 16 of its 49-80 keys and the
    # rings of 32 rows have wrapped twice. ``ok`` at the tiny size with
    # N(0, 0.02) weights says little; here the lines exist and each
    # control is further from the reference than the emulation
    assert by["reference"]["tokens"] == 80
    detail = by["reference_detail"]
    assert by["reference"]["tolerance"] == detail["tolerance"]   # its own
    assert detail["positions_compared"] == 32
    assert 0.0 <= detail["routing_flip_share"] <= 1.0
    for control in ("control", "window_control", "rope_control"):
        assert detail[f"{control}_max_abs_err"] > 0, control
    assert last["attempted"] > 0 and not last["failed"]
    assert by["window"]["counters"]["serve.decode.tokens"] > 0
    if trace:
        counters = by["traced"]["counters"]
        for name in COUNTERS:
            assert counters[name] > 0, name
        assert counters["serve.decode.attn.attended_rows"] \
            < counters["serve.decode.attn.live_rows"]
        assert 30.0 < last["metrics"]["swa.attended_share_of_keys"][
            "value"] < 100.0
        # the CPU's trace has no XLA Ops line: the readers over the
        # device trace find nothing and the line leaves them out
        for name in METRICS[1:]:
            assert name not in last["metrics"]
    after = _files(root)
    assert all(after[p] == data for p, data in before.items())
    added = sorted(str(p.relative_to(root)) for p in set(after) - set(before))
    assert added == ["chipbench/tests/rehearsal/configs/tiny-afmoe.json",
                     "chipbench/tests/rehearsal/traffic/tiny-mixedctx.json"]


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "trinity-mini.json")) as f:
        return json.load(f)


def _arch():
    return manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", "afmoe.py"))


def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    cfg = _published()
    catalog = {     # architectures.jsonl, Trinity-Mini: every number
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "moe_intermediate_size": 1024, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "route_scale": 2.826, "sliding_window": 2048,
        "topk_group": 1, "vocab_size": 200192}
    differs = sorted(k for k, v in catalog.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == [
        "num_dense_layers", "num_hidden_layers", "vocab_size"]
    assert {k: catalog[k] for k in differs} == cfg["published"]
    assert len(cfg["layer_types"]) == 32 and cfg["layers_run"] == [
        0, 4, 5, 6, 7]
    assert cfg["layer_types_run"] == [cfg["layer_types"][i]
                                      for i in cfg["layers_run"]]
    assert cfg["layer_types_run"].count("full_attention") == 1
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "trinity-mini")
    assert sorted(entry["reduced"]) == differs
    assert entry["source"] == cfg["source"]


def test_costs_against_a_count_by_hand():
    arch, cfg = _arch(), _published()
    assert arch.kv_row_bytes(cfg) == 2048
    assert arch.moe_expert_bytes(cfg) == 3 * 2048 * 1024 * 2
    got = arch.costs(cfg, 8, 1024, 8000.0)
    assert set(got) == {"decode_step", "window_step", "attn_window",
                        "gqa_row", "moe_expert"}
    attn = 2048 * 9216 + 4096 * 2048
    assert round(attn / 1e6, 1) == 27.3
    outside = 5 * attn + 3 * 2048 * 6144 \
        + 4 * (3 * 2048 * 1024 + 2048 * 128) + 25024 * 2048
    # S = 1: 8 tokens' choices touch 128 * (1 - (15/16)**8) = 51.6
    # experts a layer; each slot's query attends 8,000.5 rows on the
    # full layer and 2,048 on each of the four sliding layers
    touched = 128 * (1 - (1 - 8 / 128) ** 8)
    assert 51 < touched < 52
    state = 8 * (8001 + 4 * 2049) * 2048
    moved = 5 * 8 * 2 * 4096 * 2 + 5 * 8 * 2048
    want = outside * 2 + 4 * touched * 12582912 + 8 * 2048 * 2 + state \
        + moved + 8 * 25024 * 4
    assert got["decode_step"]["bytes"] == pytest.approx(want, rel=1e-12)
    assert 3.0e9 < want < 3.6e9             # the 3.1-3.4 GB of the S=1 step
    # the window: 8,192 rows; its attention at 8,000 live rows attends
    # 8,512 keys a query on the full layer, 2,048 on a sliding one
    assert 7e12 < got["window_step"]["flops"] < 10e12
    assert got["attn_window"]["flops"] == pytest.approx(
        8192 * (8512 + 4 * 2048) * 4 * 4096, rel=1e-12)
    assert got["attn_window"]["bytes"] == pytest.approx(
        8 * (9024 + 4 * 3072) * 2048 + 5 * 8192 * 2 * 4096 * 2
        + 5 * 8192 * 2048, rel=1e-12)


def test_the_three_controls_are_further_than_the_emulation():
    """At a tiny size on the CPU: the reference with every matmul
    operand in float8, without the window, and with rotary on the full
    layer are each far from the reference at positions past the window;
    its bfloat16 emulation is near at the median position."""
    import jax.numpy as jnp
    import numpy as np
    arch = _arch()
    with open(os.path.join(FIXTURE, "configs", "tiny-afmoe.json")) as f:
        cfg = json.load(f)
    os.environ.setdefault("MXNET_KERNEL_TIER", "xla")
    symbol = arch.decode_symbol(cfg, 1)
    params = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(str(a.dtype) == "bfloat16" for a in params.values())
    assert np.asarray(params["lm_l0_ln1_gamma"], np.float32).all()
    assert params["lm_l1_moe_gate_weight"].shape == (16, 64, 32)
    assert params["lm_l1_moe_router_bias"].shape == (16,)
    assert params["lm_l0_qkvg_weight"].shape == (2 * (8 + 2) * 16, 64)
    for name in params:            # weights of real size: see evabyte
        if name.endswith("_weight"):
            params[name] = (np.asarray(params[name], np.float32) * 10) \
                .astype(params[name].dtype)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 80)).astype("i4")
    from chipbench.reference import afmoe as ref
    rcfg = dict(cfg, layer_types=cfg["layer_types_run"])
    want = np.asarray(ref.forward(params, tokens, rcfg))
    per_position = lambda x: np.median(              # noqa: E731
        np.max(np.abs(x - want[:, -32:]), axis=-1))
    fp8 = per_position(np.asarray(ref.forward(
        params, tokens, rcfg, round_to=jnp.float8_e4m3fn, tail=32)))
    dense = per_position(np.asarray(ref.forward(
        params, tokens, rcfg, window=False, tail=32)))
    rope = per_position(np.asarray(ref.forward(
        params, tokens, rcfg, rope_full=True, tail=32)))
    emu = per_position(np.asarray(ref.forward(
        params, tokens, rcfg, round_to=jnp.bfloat16, tail=32)))
    assert emu < fp8 / 4 and emu < dense / 4 and emu < rope / 4, \
        (emu, fp8, dense, rope)
