"""The Granite 4.0-H architecture (archs/granite_hybrid.py,
reference/granite_hybrid.py, the configuration granite-4.0-h-micro, the
traffic mix chat32-closed, the ``ssm.*`` / ``ssm_*`` metrics) on the
CPU: the interface, the configuration against the catalog and its
arithmetic, the costs against a count by hand, every new reader on a
synthetic ``obs``, the decays ``make_params`` draws - quick, and part of
tier-1 through ``tests/test_granite_hybrid.py`` - and the cell rehearsed
at a tiny size (tests/fixtures/granite_hybrid/) in a temporary copy of
the rehearsal manifest, traced and untraced - by hand, two CPU
rehearsals of a minute each."""
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

from chipbench import manifest, traffic as traffic_mod  # noqa: E402
from chipbench.tests import scripted_trace  # noqa: E402

REHEARSAL = os.path.join(HERE, "rehearsal")
FIXTURE = os.path.join(HERE, "fixtures", "granite_hybrid")
CELL = "tiny-granite-chat32"
REAL_CELL = "granite4h-serve-chat32-closed"
NEW_METRICS = ("ssm.share_of_step", "ssm_decode_roofline",
               "ssm_window_roofline")
#: architectures.jsonl, row granite-4.0-h-micro: ``config``
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(FIXTURE, "configs", "tiny-granite.json")) as f:
        return json.load(f)


def _arch():
    return manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs",
                             "granite_hybrid.py"))


# ------------------------------------------------------------ quick cases
def test_the_configuration_is_the_catalogs_and_nothing_is_reduced():
    cfg = _published()
    assert [k for k, v in CATALOG.items() if cfg.get(k) != v] == []
    assert cfg["layer_types"].count("mamba") == 36
    assert [i for i, k in enumerate(cfg["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert cfg["reduced"] == []
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "granite-4.0-h-micro")
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    for key in ("reduced_detail", "assumed", "deployment", "env"):
        assert cfg[key], key
    # one entry of ``assumed`` for each choice the config leaves open
    for key in ("split_order", "convolution", "dt_limits", "gated_norm",
                "D", "multipliers", "precision", "state_layout", "weights",
                "ladder", "capacity", "prefill_chunk"):
        assert key in cfg["assumed"], key
    assert "float32" in cfg["assumed"]["precision"]
    assert cfg["env"] == {"MXNET_KERNEL_TIER": "pallas"}
    assert (cfg["capacity"], cfg["prefill_chunk"], cfg["ladder"]) \
        == (4096, 256, [1, 8, 32])
    assert cfg["prefill_chunk"] == cfg["mamba_chunk_size"]
    # the arithmetic of reduced_detail, in millions of parameters
    D, F, V = 2048, 8192, 100352
    d_in, C = 64 * 64, 64 * 64 + 2 * 128
    ffn = 3 * D * F
    mamba = D * (d_in + C + 64) + d_in * D + C * 5 + 3 * 64 + d_in \
        + 2 * D + ffn
    attn = D * (32 + 2 * 8) * 64 + 32 * 64 * D + 2 * D + ffn
    total = 36 * mamba + 4 * attn + V * D + D
    assert [round(x / 1e6, 2) for x in (mamba, attn, V * D / 1.0)] \
        == [76.18, 60.82, 205.52]
    assert round(total / 1e6) == 3191 and round(2 * total / 1e9, 2) == 6.38
    recurrent = 36 * 4 * (64 * 64 * 128 + 3 * C)
    kv = 4 * cfg["capacity"] * 2048
    assert round(recurrent / 1e6, 1) == 77.4 and round(kv / 1e6, 1) == 33.6
    state = 41 * (recurrent + kv)
    assert round(state / 1e9, 2) == 4.55
    assert 0.67 < (2 * total + state) / 16e9 < 0.69
    # an S = 1 step of 32 slots: the mixer's weights and its state are
    # 59 % of what it reads
    step = 2 * total + 32 * recurrent * 2
    mixer = 36 * 2 * (D * (d_in + C + 64) + d_in * D) + 32 * recurrent * 2
    assert 0.58 < mixer / step < 0.61


def test_the_traffic_is_the_issues():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    mix = cell.traffic
    assert cell.chips == 1 and cell.config["name"] == "granite-4.0-h-micro"
    assert mix["block"][:4] == [[40, 256], [1900, 96], [150, 384],
                                [640, 160]]
    assert mix["block"][-3:] == [[1360, 120], [900, 304], [1780, 88]]
    assert "prefix" not in mix
    assert (mix["kind"], mix["clients"], mix["lead_in_blocks"],
            mix["trace_seconds"]) == ("closed_loop", 32, 1, 4)
    assert mix["clients"] == max(cell.config["ladder"])
    assert traffic_mod.block_totals(mix) == (32, 26252, 7704)
    assert sum(p == 1900 for p, _ in mix["block"]) == 4
    assert not [p for p, _ in mix["block"] if p % 256 == 0]
    assert max(p + a for p, a in mix["block"]) == 1996 \
        < cell.config["capacity"]
    names = {m.name for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"engine.step_ms_p50", "engine.fetch_ms_p50.chat",
            "sched.runahead_share_of_steps", "attn.read_share_of_step",
            "decode_program_roofline",
            "sched.riding_share_of_window_slots",
            "engine.real_share_of_window_rows",
            "attn.live_share_of_capacity.chat"} <= names
    assert not [n for n in names if n.startswith(("moe", "gqa_", "mla",
                                                  "dsa", "eva", "mhc"))]
    # the three metrics that read null since PR 46 are left alone
    assert not names & {"engine.launch_latency_ms_p50.chat",
                        "engine.wake_latency_ms_p50.chat",
                        "sched.turnaround_ms_p50.chat"}
    assert {m.name for m in cell.end_to_end} == {
        "serve_tokens_per_s", "serve_ttft_p90_ms", "setup_s"}
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"][0] == REAL_CELL   # later cells join
            assert (m["moves"], m["source"], m["layer"]) == (
                "serve_tokens_per_s", "device_trace", "kernels")
    assert len(man["workloads"]) >= 11          # eleven when PR 48 wrote it
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1


@pytest.mark.parametrize("step_len", [1, 16])
def test_the_architecture_file_has_the_interface_and_builds_the_block(
        step_len):
    arch, cfg = _arch(), _tiny()
    for name in manifest.ARCH_INTERFACE["serve"]:
        assert hasattr(arch, name), name
    sym = arch.decode_symbol(cfg, step_len)
    ops = [n.op for n in sym._topo_nodes() if not n.is_variable]
    assert ops.count("ssm_mixer_decode") == 2
    assert ops.count("attention_decode") == 1 and "MoEFFN" not in ops
    args = sym.list_arguments()
    assert "fed" in args and "pos_ids" not in args
    assert "lm_head_weight" not in args             # tied
    assert {"lm_l0_mamba_in_weight", "lm_l0_mamba_conv_weight",
            "lm_l0_mamba_conv_bias", "lm_l0_mamba_dt_bias", "lm_l0_mamba_A_log",
            "lm_l0_mamba_D", "lm_l0_mamba_norm_gamma",
            "lm_l1_qkv_weight"} <= set(args)
    assert arch.data_shapes(cfg, 4, step_len) == {"data": (4, step_len),
                                                  "fed": (4,)}
    with pytest.raises(SystemExit, match="published block"):
        arch.decode_symbol(dict(cfg, tie_word_embeddings=False), step_len)
    with open(os.path.join(ROOT, "chipbench", "reference",
                           "granite_hybrid.py")) as f:
        text = f.read()
    body = text.split('"""', 2)[2]                  # prose may name them
    assert "mxnet_tpu" not in body and "pallas" not in body
    assert "chunk" not in body and "cache" not in body
    assert "lax.scan" in body                       # step by step
    assert 'default_matmul_precision("highest")' in text
    # every assumption at the head of the reference too
    for word in ("z | xBC | dt", "SiLU after it", "(0, inf)",
                 "BEFORE the statistic", "one scalar a head",
                 "not the tied", "no positions of any kind"):
        assert word in text.split('"""', 2)[1], word


def test_costs_against_a_count_by_hand():
    arch, cfg = _arch(), _published()
    assert arch.kv_row_bytes(cfg) == 2048
    assert arch.ssm_state_bytes(cfg) == 4298752
    row = arch.ssm_row(cfg)
    assert row == {"flops": 4259840.0, "bytes": 25216}
    got = arch.costs(cfg, 32, 256, 1000.0)
    assert set(got) == {"decode_step", "window_step", "ssm_state",
                        "ssm_row"}
    assert got["ssm_state"] == {"flops": 0.0, "bytes": 4298752}
    weights = got["decode_step"]["weights"]
    assert round(weights / 1e6) == 3191
    # an S = 1 step of 32 slots at a context of 1,000: the weights, 32 x
    # 36 states read and written (4.95 GB), 4 layers' live K/V
    want = weights * 2 + 32 * 2048 * 2 + 32 * 36 * 4298752 \
        + 32 * 36 * 25216 + 4 * (32 * 1001 + 32) * 2048 + 32 * 100352 * 4
    assert got["decode_step"]["bytes"] == pytest.approx(want, rel=1e-12)
    assert 32 * 36 * 4298752 == pytest.approx(4.95e9, rel=0.005)
    assert 11.4e9 < got["decode_step"]["bytes"] < 11.8e9
    # a packed window's 384 rows: 2.45 TFLOP of matmul
    packed = arch.step(cfg, 32, 256, 1000.0, rows=384)
    assert 2 * 384 * weights == pytest.approx(2.45e12, rel=0.01)
    assert packed["flops"] > 2.45e12
    # the chunk's own work is 3 % of a layer's matmuls
    assert 0.025 < row["flops"] / (2 * 76.18e6) < 0.03


def _obs(**kw):
    obs = {"events": scripted_trace.events(), "device_kind": "TPU v5 lite",
           "chips": 1, "ring": [], "counters": {}, "cost": {}}
    obs.update(kw)
    return obs


def _decode_trace():
    """Chip 0: the S=1 program of the top rung runs twice for 1,000 us,
    inside each run four mixers' ssm_conv 5 us and ssm_update 100 us;
    the window program runs once for 2,000 us with four times ssm_conv
    10 us, ssm_update 100 us and ssm_scan 40 us."""
    e = scripted_trace._e
    plane, out = "/device:TPU:0", []
    for base in (0, 2000):
        out.append(e(plane, "XLA Modules", "jit_fwd_infer_32x1(abc)", base,
                     1000))
        out.append(e(plane, "XLA Modules", "jit_fwd_infer_8x1(abd)",
                     base + 1000, 10))
        for layer in range(4):
            out.append(e(plane, "XLA Ops", f"ssm_conv.{layer}",
                         base + 200 * layer, 5))
            out.append(e(plane, "XLA Ops", f"ssm_update.{layer}",
                         base + 200 * layer + 10, 100))
        out.append(e(plane, "XLA Ops", "fusion.1", base + 900, 10))
    out.append(e(plane, "XLA Modules", "jit_fwd_infer_32x256(abe)", 5000,
                 2000))
    for layer in range(4):
        out.append(e(plane, "XLA Ops", f"ssm_conv.{layer + 9}",
                     5000 + 400 * layer, 10))
        out.append(e(plane, "XLA Ops", f"ssm_update.{layer + 9}",
                     5020 + 400 * layer, 100))
        out.append(e(plane, "XLA Ops", f"ssm_scan.{layer + 9}",
                     5200 + 400 * layer, 40))
    return out


def test_every_new_reader_on_a_synthetic_obs():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    metrics = {m.name: m for m in cell.per_layer if m.name in NEW_METRICS}
    assert sorted(metrics) == sorted(NEW_METRICS)
    from chipbench import readers
    read = lambda name, obs: readers.read(metrics[name], obs)  # noqa: E731
    # a program without the operations and the ring's fields (the
    # parent): every reader finds nothing, and raises nothing
    for name in NEW_METRICS:
        assert read(name, _obs()) is None, name
        assert read(name, {}) is None, name
    ring = [{"kind": "serve.decode.step", "window": 1, "rung": 32,
             "ssm_rows": 4 * n, "ssm_touched": 4 * n}
            for n in (30, 32, 32)] + [
        {"kind": "serve.decode.step", "window": 256, "rung": 32,
         "ssm_rows": 4 * rows, "ssm_touched": 4 * 32}
        for rows in (384, 380, 200)] + [
        {"kind": "serve.decode.step", "window": 256, "rung": 8,
         "ssm_rows": 4 * 256, "ssm_touched": 4 * 8}]
    cost = {"ssm_state": {"flops": 0.0, "bytes": 4298752},
            "ssm_row": {"flops": 4259840.0, "bytes": 25216}}
    obs = _obs(events=_decode_trace(), ring=ring, cost=cost)
    # 4 x (5 + 100) us of ssm_* in each 1,000 us run of the 32-slot one
    assert read("ssm.share_of_step", obs) == pytest.approx(42.0)
    # median 128 states x 4,298,752 B at 819 GB/s = 672 us against
    # 420 us of ssm_* a run: a scripted trace reads what it likes
    assert read("ssm_decode_roofline", obs) == pytest.approx(
        100.0 * (128 * 4298752 / 819e9) / 420e-6, rel=1e-9)
    # the top rung's median window touches 128 states too and advances
    # 4 x 380 rows, against 600 us of ssm_* a run of the window program
    # (the rung-8 record, 32 states, is not the top rung's); bytes bind
    assert read("ssm_window_roofline", obs) == pytest.approx(
        100.0 * ((128 * 4298752 + 4 * 380 * 25216) / 819e9) / 600e-6,
        rel=1e-9)
    # and operations where a row's are many
    dear = dict(cost, ssm_row={"flops": 1e12, "bytes": 25216})
    assert read("ssm_window_roofline", dict(obs, cost=dear)) \
        == pytest.approx(100.0 * (4 * 380 * 1e12 / 197e12) / 600e-6,
                         rel=1e-9)
    # an architecture that states no such cost: not these metrics'
    for name in NEW_METRICS[1:]:
        assert read(name, dict(obs, cost={})) is None
    # a program whose ring lacks the fields (no op declares them)
    bare = [{k: v for k, v in r.items() if not k.startswith("ssm_")}
            for r in ring]
    for name in NEW_METRICS[1:]:
        assert read(name, dict(obs, ring=bare)) is None


def test_make_params_draws_decays_of_one_to_a_thousand_tokens():
    """A token's decay ``exp(-softplus(dt_bias) exp(A_log))`` lies
    between 0.2 and 0.999 a head, D and the gains are 1, the same seed
    draws the same parameters, and the reference's tail is its full
    forward's."""
    import numpy as np
    arch, cfg = _arch(), _tiny()
    os.environ.setdefault("MXNET_KERNEL_TIER", "xla")
    symbol = arch.decode_symbol(cfg, 1)
    params = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(str(a.dtype) == "bfloat16" for a in params.values())
    again = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(np.array_equal(params[n], again[n]) for n in params)
    f32 = lambda n: np.asarray(params[n], np.float32)       # noqa: E731
    decays = []
    for layer in (0, 2):
        dt = np.log1p(np.exp(f32(f"lm_l{layer}_mamba_dt_bias")))
        assert (dt > 0.9e-3).all() and (dt < 0.11).all()
        a = np.exp(f32(f"lm_l{layer}_mamba_A_log"))
        assert (a >= 0.99).all() and (a <= 16.1).all()
        decays.append(np.exp(-dt * a))
        assert (f32(f"lm_l{layer}_mamba_D") == 1).all()
        assert (f32(f"lm_l{layer}_mamba_norm_gamma") == 1).all()
        w = f32(f"lm_l{layer}_mamba_conv_weight")
        assert w.shape == (96, 4) and 0.2 < w.std() < 0.35
    decays = np.concatenate(decays)
    assert (decays > 0.2).all() and (decays < 0.9995).all()
    assert 0.01 < f32("lm_tok_embed_weight").std() < 0.03
    import jax.numpy as jnp
    from chipbench.reference import granite_hybrid as ref
    tokens = np.random.default_rng(0).integers(0, 64, (2, 80)).astype("i4")
    want = np.asarray(ref.forward(params, tokens, cfg, tail=32))
    assert want.shape == (2, 32, 64)
    np.testing.assert_allclose(
        want, np.asarray(ref.forward(params, tokens, cfg))[:, -32:],
        atol=1e-5, rtol=1e-5)
    # the controls are switches of the same forward
    controls = arch._controls(cfg)
    for _key, _what, switches in controls:
        low = np.asarray(ref.forward(params, tokens, cfg, tail=32,
                                     **switches))
        assert np.abs(low - want).max() > 0
    assert [k for k, _w, _s in controls] == [
        "fp8", "state_bf16", "state_none", "state_lost"]
    assert jnp.bfloat16 in [s.get("state_dtype") for _k, _w, s in controls]
    # the two that break the state: nothing carried, and the hand-over
    # between two windows (the tiny configuration's are 16 tokens) lost
    assert [s.get("state_every") for _k, _w, s in controls][2:] == [1, 16]
    # a drop at a multiple beyond the sequence's end changes nothing
    same = np.asarray(ref.forward(params, tokens, cfg, tail=32,
                                  state_every=1000))
    assert np.array_equal(same, want)


# ---------------------------------------------------- the cell, rehearsed
def _add_tiny_granite(root):
    """The tiny configuration, its traffic mix and its cell into the
    copy under ``root``: two new files and manifest entries; the
    architecture, its reference and the metrics' readers are the
    benchmark's own."""
    inside = "chipbench/tests/rehearsal/"
    for kind, name in (("configs", "tiny-granite.json"),
                       ("traffic", "tiny-chat32.json")):
        dest = root / inside / kind / name
        assert not dest.exists()
        shutil.copy(os.path.join(FIXTURE, kind, name), dest)
    man = manifest.load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    man["paths"] = [inside.rstrip("/")]
    for c in man["configs"]:
        c["file"] = inside + c["file"]
    man["configs"].append({
        "name": "tiny-granite", "source": "fixture", "reduced": [],
        "file": inside + "configs/tiny-granite.json", "why": "rehearsal"})
    man["workloads"].append({
        "name": CELL, "config": "tiny-granite", "traffic": "tiny-chat32",
        "chips": 1, "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "serve_ttft_p90_ms",
                         "sched.tokens_per_iter", "sched.window_iter_share",
                         "engine.step_ms_p50"):
            m["workloads"].append(CELL)
    real = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in NEW_METRICS:
        man["per_layer"].append(dict(real[name], workloads=[CELL]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))


@pytest.fixture(scope="module")
def copy_with_granite(tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):       # the system under test
        os.symlink(os.path.join(ROOT, program), root / program)
    _add_tiny_granite(root)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_granite_rehearses(copy_with_granite, trace):
    root = copy_with_granite
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
    assert last["correct"] and by["reference"]["tokens"] == 80
    detail = by["reference_detail"]
    assert by["reference"]["tolerance"] == detail["tolerance"]   # its own
    assert detail["positions_compared"] == 32
    for key in ("fp8", "state_bf16"):
        assert detail[f"{key}_control_max_abs_err"] > 0
    assert last["attempted"] > 0 and not last["failed"]
    assert by["window"]["counters"]["serve.decode.tokens"] > 0
    assert not by["window"]["compiles_in_window"]
    if trace:
        counters = by["traced"]["counters"]
        for name in ("serve.decode.ssm.rows", "serve.decode.ssm.touched",
                     "serve.decode.attn.live_rows"):
            assert counters[name] > 0, name
        # two mamba layers advance every real row of a dispatch
        assert counters["serve.decode.ssm.rows"] \
            > counters["serve.decode.ssm.touched"]
        # the CPU's trace has no XLA Ops line: the readers over the
        # device trace find nothing and the line leaves them out
        for name in NEW_METRICS:
            assert name not in last["metrics"]
