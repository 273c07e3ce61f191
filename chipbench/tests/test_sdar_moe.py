"""The benchmark's own tests of the SDAR architecture (ISSUE 60), on the
CPU: the interface with both optional names (``decode_step_len``,
``mask_token``: the first architecture to state them), the configuration
against the catalog and its arithmetic, the costs against a count by
hand, every new reader on a synthetic ``obs``, the traffic file against
the issue's eight pairs, ``make_params`` and the controls - and the cell
rehearsed at a tiny size (tests/fixtures/sdar_moe/) in a temporary copy
of the rehearsal manifest, traced: one CPU rehearsal of under a minute
whose ``reference`` line reads ``decode_step_len`` 4 and ``masked_feeds``
above 0. All of it is part of tier-1 through
``tests/test_chipbench_sdar_moe.py``."""
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
FIXTURE = os.path.join(HERE, "fixtures", "sdar_moe")
CELL = "tiny-sdar-mathchat"
REAL_CELL = "sdar30b-serve-mathchat-closed"
NEW_METRICS = (
    "diffusion.feeds_per_block", "diffusion.tokens_per_feed",
    "diffusion.dropped_share_of_rows", "sched.block_iter_share",
    "engine.block_step_ms_p50", "engine.block_dispatch_ms_p50",
    "engine.block_fetch_ms_p50", "engine.denoise_ms_p50",
    "block_step_roofline", "moe_block_roofline", "gqa_block_roofline")
#: the accepted metrics whose reading is true of a block engine
SHARED_METRICS = ("sched.tokens_per_iter", "engine.fetched_kb_per_iter.chat")
#: architectures.jsonl, row SDAR-30B-A3B-Chat: ``config``
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
PAIRS = [(131, 512), (1400, 256), (421, 1024), (262, 770), (903, 384),
         (90, 641), (613, 896), (1150, 323)]


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(FIXTURE, "configs", "tiny-sdar.json")) as f:
        return json.load(f)


def _arch():
    return manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", "sdar_moe.py"))


# ------------------------------------------------------------ quick cases
def test_the_architecture_file_has_the_interface_with_both_optional_names():
    arch, cfg, tiny = _arch(), _published(), _tiny()
    for name in manifest.ARCH_INTERFACE["serve"] \
            + manifest.ARCH_OPTIONAL["serve"]:
        assert hasattr(arch, name), name
    assert arch.decode_step_len(cfg) == 4 and arch.mask_token(cfg) == 151669
    assert arch.decode_step_len(tiny) == 4 and arch.mask_token(tiny) == 63
    from mxnet_tpu.models import transformer as tfm
    for S in (1, 4, 16):
        sym = arch.decode_symbol(tiny, S)
        assert tfm.decode_procedure(sym) == {
            "block_length": 4, "mask_token_id": 63, "denoising_steps": 4,
            "remasking": "low_confidence_dynamic",
            "confidence_threshold": 0.9}
        assert sorted(arch.data_shapes(tiny, 4, S)) == ["data", "fed"]
        nodes = [n for n in sym._topo_nodes() if n.op == "attention_decode"]
        assert len(nodes) == 2 and all(
            (n.attrs["block"], n.attrs["kv_heads"], n.attrs["rope"])
            == (4, 2, True) for n in nodes)
    with pytest.raises(SystemExit, match="builds the published block"):
        arch.decode_symbol(dict(tiny, use_sliding_window=True), 1)
    assert 0 < arch.LOGIT_TOL < 1 and arch._TAIL == 32


def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    cfg = _published()
    assert [k for k, v in CATALOG.items() if cfg.get(k) != v] \
        == ["num_hidden_layers"] == cfg["reduced"]
    assert cfg["num_hidden_layers"] == 6 \
        and cfg["published"] == {"num_hidden_layers": 48}
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "sdar-30b-a3b-chat")
    assert entry["reduced"] == cfg["reduced"] \
        and entry["source"] == cfg["source"] \
        == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/" \
           "config.json"
    assert (cfg["kind"], cfg["arch"]) == ("serve", "sdar_moe")
    assert (cfg["capacity"], cfg["prefill_chunk"], cfg["ladder"]) \
        == (8192, 512, [1, 4, 8])
    assert cfg["env"] == {"MXNET_KERNEL_TIER": "pallas"}
    assert (cfg["compute_dtype"], cfg["param_dtype"]) \
        == ("bfloat16", "bfloat16")
    # what config.json does not state, each with its reason
    assert (cfg["block_length"], cfg["denoising_steps"], cfg["remasking"],
            cfg["confidence_threshold"], cfg["mask_token_id"]) \
        == (4, 4, "low_confidence_dynamic", 0.9, 151669)
    for key in ("block_length", "denoising_steps", "remasking",
                "confidence_threshold", "mask_token_id", "generation",
                "qk_norm", "logits", "schedule_under_seeded_weights",
                "weights", "ladder", "capacity", "prefill_chunk",
                "sampling", "kernel_tier"):
        assert key in cfg["assumed"], key
    assert "MEMORY" in cfg["assumed"]["mask_token_id"]
    assert "5 feeds a block" in cfg["assumed"][
        "schedule_under_seeded_weights"]
    assert cfg["prefill_chunk"] % cfg["block_length"] == 0 \
        and cfg["capacity"] % cfg["block_length"] == 0
    assert "eight pipeline stages of six" in cfg["deployment"]
    assert sorted(cfg["reduced_detail"]) == ["arithmetic",
                                             "num_hidden_layers"]
    # the arithmetic of reduced_detail, in millions of parameters
    D, V = 2048, 151936
    attn = D * (32 + 2 * 4) * 128 + 32 * 128 * D
    expert, router = 3 * D * 768, D * 128
    layer = attn + router + 128 * expert
    assert [round(x / 1e6, 2) for x in (attn, router, expert, 128 * expert,
                                        layer, 2 * V * D)] \
        == [18.87, 0.26, 4.72, 603.98, 623.12, 622.33]
    here = 6 * layer + 2 * V * D
    assert round(here / 1e9, 3) == 4.361 and round(2 * here / 1e9, 2) == 8.72
    state = 13 * cfg["capacity"] * 6 * 2 * 4 * 128 * 2
    assert round(state / 1e9, 2) == 1.31
    assert 0.62 < (2 * here + state) / 16e9 < 0.64
    # a seventh layer would fit; the floor (25 % of the chip) is cleared
    # two and a half times over without it
    assert 2 * here + state + 2 * layer < 16e9


def test_the_traffic_is_the_issues():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    mix = cell.traffic
    assert cell.chips == 1 and cell.config["name"] == "sdar-30b-a3b-chat"
    assert [tuple(p) for p in mix["block"]] == PAIRS
    assert (mix["kind"], mix["clients"], mix["lead_in_blocks"],
            mix["trace_seconds"]) == ("closed_loop", 8, 1, 4)
    assert mix["clients"] == max(cell.config["ladder"]) and "prefix" not in mix
    assert traffic_mod.block_totals(mix) == (8, 4970, 4806)
    assert sorted({p % 4 for p, _ in PAIRS}) == [0, 1, 2, 3]
    assert sum(n % 4 != 0 for _, n in PAIRS) == 3
    assert max(p + n for p, n in PAIRS) == 1656 < cell.config["capacity"]
    names = {m.name for m in cell.per_layer}
    assert set(NEW_METRICS) | set(SHARED_METRICS) == names
    # time to first token is not this cell's to judge: its 90th
    # percentile spreads by 13 % over seeds (PERF.md section 6, PR 60),
    # so nothing that moves it lists the cell either
    assert {m.name for m in cell.end_to_end} == {
        "serve_tokens_per_s", "setup_s"}
    assert not [m["name"] for m in man["per_layer"]
                if REAL_CELL in m["workloads"]
                and m["moves"] != "serve_tokens_per_s"]
    layers = {"diffusion": "DecodeScheduler", "sched": "DecodeScheduler",
              "engine": "DecodeEngine"}
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [REAL_CELL]
            assert m["moves"] == "serve_tokens_per_s"
            assert m["layer"] == layers.get(m["name"].split(".")[0],
                                            "kernels")
    # what keys on ``window == 1`` / ``> 1`` or on fwd_infer_<slots>x1
    # is not true of a block dispatch (window 4), and what reads nothing
    # where nothing runs ahead is not listed
    for m in man["per_layer"]:
        if m["name"] in ("sched.window_iter_share", "engine.step_ms_p50",
                         "engine.window_ms_p50.chat",
                         "engine.dispatch_ms_p50.chat", "moe_expert_roofline",
                         "gqa_decode_roofline", "decode_program_roofline",
                         "sched.runahead_share_of_steps",
                         "sched.runahead_share_of_windows",
                         "sched.queue_wait_ms_p90.chat",
                         "engine.head_share_of_window_rows"):
            assert REAL_CELL not in m["workloads"], m["name"]


@pytest.mark.parametrize("step_len", [4, 512])
def test_costs_against_a_count_by_hand(step_len):
    arch, cfg = _arch(), _published()
    slots, live = 8, 1000.0
    cost = arch.costs(cfg, slots, 512, live)
    assert sorted(cost) == ["block_step", "block_step_fixed", "decode_step",
                            "gqa_row", "moe_expert", "window_step"]
    assert cost["decode_step"] == cost["block_step"]    # the step IS a block
    # the same step with no expert read: the reader adds the measured
    fixed = cost["block_step_fixed"]
    assert fixed["flops"] == cost["block_step"]["flops"]
    assert cost["block_step"]["bytes"] - fixed["bytes"] == pytest.approx(
        6 * cost["block_step"]["experts_touched_per_layer"] * 9437184)
    assert cost["gqa_row"]["bytes"] == 2 * 4 * 128 * 2 == 2048
    assert cost["moe_expert"]["bytes"] == 3 * 2048 * 768 * 2 == 9437184
    step = cost["block_step"] if step_len == 4 else cost["window_step"]
    tokens = slots * step_len
    touched = 128 * (1 - (1 - 8 / 128) ** tokens)
    assert step["experts_touched_per_layer"] == pytest.approx(touched)
    if step_len == 4:
        assert round(touched, 1) == 111.8
    attn_w = 2048 * 40 * 128 + 4096 * 2048
    outside = 6 * (attn_w + 2048 * 128) + 151936 * 2048
    keys = live + step_len
    want_bytes = (2 * outside + 6 * touched * 9437184 + tokens * 2048 * 2
                  + 6 * slots * keys * 2048 + 6 * tokens * 2048
                  + 6 * tokens * 2 * 4096 * 2 + tokens * 151936 * 4)
    want_flops = 2.0 * tokens * (outside + 6 * 8 * 3 * 2048 * 768) \
        + 6 * tokens * keys * 4.0 * 4096
    assert step["bytes"] == pytest.approx(want_bytes)
    assert step["flops"] == pytest.approx(want_flops)
    if step_len == 4:       # 7.3 GB, 8.9 ms at 819 GB/s: bytes bound
        assert 7.25e9 < step["bytes"] < 7.35e9
        assert step["flops"] / 197e12 < 0.1 * step["bytes"] / 819e9


def _obs(**kw):
    obs = {"events": scripted_trace.events(), "device_kind": "TPU v5 lite",
           "chips": 1, "ring": [], "counters": {}, "cost": {}}
    obs.update(kw)
    return obs


def _block_trace():
    """Chip 0: the block program of the top rung runs three times for
    10,000 us; inside each run six layers hold a ``decode_attn`` of 50
    us and two ``moe_gmm_*`` of 600 and 500 us; a rung-4 block program
    and the top rung's window program run beside it with kernels of
    their own."""
    e = scripted_trace._e
    plane, out = "/device:TPU:0", []
    for base in (0, 20000, 40000):
        out.append(e(plane, "XLA Modules", "jit_fwd_infer_8x4(abc)", base,
                     10000))
        for layer in range(6):
            at = base + 100 + 1500 * layer
            out.append(e(plane, "XLA Ops", f"decode_attn.{layer}", at, 50))
            out.append(e(plane, "XLA Ops", f"moe_gmm_gate_up.{layer}",
                         at + 100, 600))
            out.append(e(plane, "XLA Ops", f"moe_gmm_down.{layer}",
                         at + 800, 500))
    out.append(e(plane, "XLA Modules", "jit_fwd_infer_4x4(abd)", 60000, 7000))
    out.append(e(plane, "XLA Ops", "moe_gmm_down.77", 60100, 3000))
    out.append(e(plane, "XLA Modules", "jit_fwd_infer_8x512(abe)", 70000,
                 60000))
    out.append(e(plane, "XLA Ops", "moe_gmm_gate_up.78", 70100, 9000))
    out.append(e(plane, "XLA Ops", "window_attn.78", 80100, 900))
    return out


def test_every_new_reader_on_a_synthetic_obs():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    metrics = {m.name: m for m in cell.per_layer if m.name in NEW_METRICS}
    assert sorted(metrics) == sorted(NEW_METRICS)
    from chipbench import readers
    read = lambda name, obs: readers.read(metrics[name], obs)  # noqa: E731
    # a program without the ring's fields and the counters (the parent):
    # every reader finds nothing, and raises nothing
    parent_ring = [{"kind": "serve.decode.step", "window": w, "rung": 8,
                    "step_us": 9000, "dispatch_us": 900, "fetch_us": 8000,
                    "moe_touched": 600, "attn_attended": 48000}
                   for w in (1, 1, 512)]
    for name in NEW_METRICS:
        for obs in ({}, _obs(), _obs(events=_block_trace(), ring=parent_ring,
                                     counters={"serve.decode.tokens": 900})):
            got = read(name, obs)
            assert got is None or (name == "sched.block_iter_share"
                                   and got == 0.0), (name, got)
    block = {"kind": "serve.decode.step", "window": 4, "block": 4, "rung": 8,
             "ahead": 0, "tentative": 7}
    ring = [dict(block, step_us=s, dispatch_us=d, fetch_us=f, denoise_us=n,
                 moe_touched=t, attn_attended=a, decided=7)
            for s, d, f, n, t, a in (
                (12000, 900, 11000, 10500, 660, 48000),
                (13000, 1000, 11900, 11400, 672, 48192),
                (14000, 1100, 12800, 12300, 684, 48384))] + [
        {"kind": "serve.decode.step", "window": 512, "rung": 8,
         "step_us": 70000, "dispatch_us": 2000, "fetch_us": 67000,
         "moe_touched": 768, "attn_attended": 3000}]
    arch, cfg = _arch(), _published()
    obs = _obs(events=_block_trace(), ring=ring,
               cost=arch.costs(cfg, 8, 512, 1000.0),
               counters={"serve.decode.diffusion.feeds": 6000,
                         "serve.decode.diffusion.blocks": 1250,
                         "serve.decode.diffusion.rows_dropped": 19000,
                         "serve.decode.tokens": 4700})
    assert read("diffusion.feeds_per_block", obs) == pytest.approx(4.8)
    assert read("diffusion.tokens_per_feed", obs) == pytest.approx(4700 / 6e3)
    # 19,000 rows of 6,000 feeds x 4
    assert read("diffusion.dropped_share_of_rows", obs) \
        == pytest.approx(100.0 * 19000 / 24000)
    assert read("sched.block_iter_share", obs) == pytest.approx(75.0)
    assert read("engine.block_step_ms_p50", obs) == pytest.approx(13.0)
    assert read("engine.block_dispatch_ms_p50", obs) == pytest.approx(1.0)
    assert read("engine.block_fetch_ms_p50", obs) == pytest.approx(11.9)
    assert read("engine.denoise_ms_p50", obs) == pytest.approx(11.4)
    # the whole step: what lies outside the experts and the 672 experts
    # measured as touched, at the peak, over the top rung's 10 ms (the
    # rung-4 program and the window are not read)
    least_ms = 1e3 * (obs["cost"]["block_step_fixed"]["bytes"]
                      + 672 * 9437184) / 819e9
    assert read("block_step_roofline", obs) == pytest.approx(
        100.0 * least_ms / 10.0, rel=1e-9)
    assert 88 < read("block_step_roofline", obs) < 92
    bare = [{k: v for k, v in r.items() if k != "moe_touched"} for r in ring]
    assert read("block_step_roofline", dict(obs, ring=bare)) is None
    # 672 experts touched over 6 layers: 6.34 GB, 7.74 ms, against
    # 6 x 1,100 us of moe_gmm* a run
    assert read("moe_block_roofline", obs) == pytest.approx(
        100.0 * (672 * 9437184 / 819e9) / 6.6e-3, rel=1e-9)
    # 48,192 positions of 2,048 B: 0.12 ms against 6 x 50 us
    assert read("gqa_block_roofline", obs) == pytest.approx(
        100.0 * (48192 * 2048 / 819e9) / 0.3e-3, rel=1e-9)
    # a trace without the block program, or a cost without the entry
    for name in ("block_step_roofline", "moe_block_roofline",
                 "gqa_block_roofline"):
        assert read(name, dict(obs, events=[])) is None
        assert read(name, dict(obs, events=scripted_trace.events())) is None
        assert read(name, dict(obs, cost={})) is None
        assert metrics[name].reader is not None
    from chipbench import block_time
    assert block_time.top_rung_block_module(obs) == "jit_fwd_infer_8x4(abc)"
    assert len(block_time.block_records(obs)) == 3


def test_make_params_is_seeded_and_the_controls_are_switches():
    """``make_params`` draws bfloat16 parameters from the seed (the same
    seed the same parameters, a large seed another set); the reference's
    tail is its full forward's; each control is a switch of the same
    forward that moves the logits, and the left-behind control reads
    the blocks after a masked one alone."""
    import jax.numpy as jnp
    import numpy as np
    arch, tiny = _arch(), _tiny()
    from chipbench.reference import sdar_moe as ref
    sym = arch.decode_symbol(tiny, 1)
    shapes = arch.data_shapes(tiny, 4, 1)
    a = arch.make_params(sym, shapes, 3280000019, tiny)
    b = arch.make_params(sym, shapes, 3280000019, tiny)
    c = arch.make_params(sym, shapes, 7, tiny)
    assert sorted(a) == sorted(b) and all(
        np.array_equal(a[k], b[k]) for k in a)
    assert any(not np.array_equal(a[k], c[k]) for k in a)
    assert all(str(v.dtype) == "bfloat16" for v in a.values())
    assert float(np.asarray(a["lm_l0_q_norm_gamma"], np.float32).min()) == 1.0
    tokens = np.random.default_rng(0).integers(0, 63, (2, 48)).astype(
        np.int32)
    full = np.asarray(ref.forward(a, jnp.asarray(tokens), tiny))
    tail = np.asarray(ref.forward(a, jnp.asarray(tokens), tiny, tail=32))
    np.testing.assert_allclose(tail, full[:, -32:], atol=1e-6)
    for control in ({"causal": True}, {"round_to": jnp.float8_e4m3fn},
                    {"round_to": jnp.bfloat16}):
        other = np.asarray(ref.forward(a, jnp.asarray(tokens), tiny,
                                       **control))
        assert np.max(np.abs(other - full)) > 1e-4, control
    ids, read = arch._left_behind(jnp.asarray(tokens), tiny, 32)
    ids, read = np.asarray(ids), np.asarray(read)
    assert (ids[:, :16] == tokens[:, :16]).all()
    assert read.tolist() == ([False] * 4 + [True] * 4) * 4
    changed = (ids != tokens).any(axis=0)[16:]
    assert not changed[read].any() and changed[~read].sum() >= 6
    assert (ids[:, 16:][:, ::2][:, ~read[::2]] == 63).all()
    # the logits as check_reference reads them: the tail, zeros before
    out = arch.reference_logits(a, jnp.asarray(tokens), tiny)
    host = np.asarray(out)
    assert host.shape == (2, 48, 64) and not host[:, :16].any()
    np.testing.assert_allclose(host[:, 16:], full[:, 16:], atol=1e-6)


# ---------------------------------------------------- the cell, rehearsed
def _add_tiny_sdar(root):
    """The tiny configuration, its traffic mix and its cell into the
    copy under ``root``: two new files and manifest entries; the
    architecture, its reference and the metrics' readers are the
    benchmark's own."""
    inside = "chipbench/tests/rehearsal/"
    for kind, name in (("configs", "tiny-sdar.json"),
                       ("traffic", "tiny-mathchat.json")):
        dest = root / inside / kind / name
        assert not dest.exists()
        shutil.copy(os.path.join(FIXTURE, kind, name), dest)
    man = manifest.load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    man["paths"] = [inside.rstrip("/")]
    for c in man["configs"]:
        c["file"] = inside + c["file"]
    man["configs"].append({
        "name": "tiny-sdar", "source": "fixture", "reduced": [],
        "file": inside + "configs/tiny-sdar.json", "why": "rehearsal"})
    man["workloads"].append({
        "name": CELL, "config": "tiny-sdar", "traffic": "tiny-mathchat",
        "chips": 1, "why": "rehearsal"})
    listed = NEW_METRICS + SHARED_METRICS
    have = {m["name"] for m in man["per_layer"]}
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("serve_tokens_per_s",) + listed:
            m["workloads"].append(CELL)
    for m in manifest.load()["per_layer"]:
        if m["name"] in listed and m["name"] not in have:
            man["per_layer"].append(dict(m, workloads=[CELL]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))


@pytest.fixture(scope="module")
def copy_with_sdar(tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):       # the system under test
        os.symlink(os.path.join(ROOT, program), root / program)
    _add_tiny_sdar(root)
    return root


def test_tiny_sdar_rehearses(copy_with_sdar):
    root = copy_with_sdar
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(root / "chipbench" / "run.py"), "--rehearse",
         "--manifest", str(root / "BENCHMARK.json"),
         "--workload", CELL, "--seed", "3280000019",
         "--seconds", "2", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    by = {l["chipbench"]: l for l in lines[:-1]}
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
    assert counters["serve.decode.runahead.launched"] == 0
    # five feeds a block but for the first blocks, which hold prompt
    # tokens (every prompt of the tiny mix is off a block's edge)
    metrics = last["metrics"]
    assert 4.0 < metrics["diffusion.feeds_per_block"]["value"] <= 5.0
    assert 70 < metrics["diffusion.dropped_share_of_rows"]["value"] <= 80
    assert 0 < metrics["sched.block_iter_share"]["value"] < 100
    # on the CPU there is no device trace: the three rooflines read
    # nothing and are left out; every counter and ring metric is there
    want = set(NEW_METRICS + SHARED_METRICS) - {
        "block_step_roofline", "moe_block_roofline", "gqa_block_roofline"}
    assert set(metrics) == want
