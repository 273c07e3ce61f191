"""The yardstick's own tests: run by hand (``python -m pytest
chipbench/tests``) and in the CPU rehearsal; not part of tier-1."""
import json
import math
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import costs, manifest, readers, stats, trace, traffic  # noqa: E402
from chipbench.tests import scripted_trace  # noqa: E402


def _mix(name):
    with open(os.path.join(ROOT, "chipbench", "traffic", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------- traffic
@pytest.mark.parametrize("shuffle", [False, True])
@pytest.mark.parametrize("name", ["chat-closed", "doc-closed"])
def test_generator_deals_whole_blocks_with_identical_totals(name, shuffle):
    mix = _mix(name)
    n, prompts, answers = traffic.block_totals(mix)
    want = sorted(tuple(p) for p in mix["block"])
    orders, prompts_seen = set(), set()
    for seed in (0, 7, 2**31 + 12345, 4_000_000_000):
        gen = traffic.requests(dict(mix, shuffle_blocks=shuffle), 50257,
                               seed)
        for b in range(3):
            block = [next(gen) for _ in range(n)]
            assert {r.block for r in block} == {b}
            assert sorted((r.prompt_len, r.max_new) for r in block) == want
            assert sum(r.prompt_len for r in block) == prompts
            assert sum(r.max_new for r in block) == answers
            assert all(len(r.prompt) == r.prompt_len for r in block)
            assert all(0 <= r.prompt.min() and r.prompt.max() < 50257
                       for r in block)
            orders.add(tuple((r.prompt_len, r.max_new) for r in block))
            prompts_seen.add(tuple(block[0].prompt[:8]))
    # the seed sets the token ids, and the order only where asked to
    assert len(prompts_seen) > 4
    assert (len(orders) > 1) == shuffle


def test_same_seed_same_requests():
    mix = _mix("chat-closed")
    a, b = traffic.requests(mix, 50257, 99), traffic.requests(mix, 50257, 99)
    for _ in range(20):
        ra, rb = next(a), next(b)
        assert (ra.prompt_len, ra.max_new) == (rb.prompt_len, rb.max_new)
        assert (ra.prompt == rb.prompt).all()


def test_chat_block_is_the_issue_s():
    assert traffic.block_totals(_mix("chat-closed"))[1:] == (2816, 976) \
        or "halved" in _mix("chat-closed")["why"]


def test_open_loop_arrivals_and_prefixes():
    mix = {"kind": "open_loop", "rate_rps": 10, "arrival": "poisson",
           "burst": {"every_s": 2.0, "size": 3},
           "prefix": {"count": 2, "len": 4}, "block": [[8, 2], [3, 2]]}
    times = traffic.arrivals(mix, 5, 10.0)
    assert times == sorted(times) and 60 < len(times) < 160
    assert times.count(2.0) == 3
    assert times == traffic.arrivals(mix, 5, 10.0)
    gen = traffic.requests(mix, 100, 5)
    reqs = [next(gen) for _ in range(8)]
    shared = [r for r in reqs if r.prefix_id]
    assert shared and all(r.prompt_len == 8 for r in shared)
    assert all(r.prefix_id is None for r in reqs if r.prompt_len == 3)
    by_id = {}
    for r in shared:
        by_id.setdefault(r.prefix_id, []).append(tuple(r.prompt[:4]))
    assert all(len(set(v)) == 1 for v in by_id.values())
    # the prefixes are dealt in turn, whatever the seed: the seed sets
    # the ids alone, never which requests share
    assert [r.prefix_id for r in shared] == [
        f"prefix-{i % 2}" for i in range(len(shared))]
    other = traffic.requests(mix, 100, 2 ** 31 + 6)
    assert [next(other).prefix_id for _ in range(8)] == [
        r.prefix_id for r in reqs]


# --------------------------------------------------------------- stats
def test_percentile_nearest_rank_and_unfinished_last():
    vals = list(range(1, 11))
    assert stats.percentile(vals, 90) == 9
    assert stats.percentile(vals, 50) == 5
    assert stats.percentile(vals, 100) == 10
    assert stats.percentile([], 90) is None
    assert stats.percentile([1.0, math.inf], 90) == math.inf
    assert stats.median([3, 1, 2]) == 2 and stats.median([1, 2, 3, 4]) == 2.5


def test_tokens_by_timestamp_on_a_scripted_timeline():
    # request A emits at 9.5, 10.0, 10.5, 11.0; B at 10.9, 12.0 (outside)
    stamps = [[9.5, 10.0, 10.5, 11.0], [10.9, 12.0], []]
    assert stats.tokens_per_s(stamps, 10.0, 12.0) == 4 / 2.0
    # a request that completes after the window still has its in-window
    # tokens credited; one that completed before it adds nothing
    assert stats.tokens_per_s([[1.0, 2.0]], 10.0, 12.0) == 0.0
    gaps = stats.token_gaps(stamps, 10.0, 12.0)
    assert sorted(round(g, 6) for g in gaps) == [0.5, 0.5, 0.5]


def test_ttft_counts_window_submits_and_failures_as_largest():
    reqs = [(9.0, 9.5, False),        # submitted before the window
            (10.0, 10.4, False), (10.5, 11.5, False),
            (11.0, None, False),      # unfinished
            (11.5, 11.6, True)]       # failed
    out = stats.ttfts(reqs, 10.0, 12.0)
    assert len(out) == 4
    assert sorted(out)[:2] == [pytest.approx(0.4), pytest.approx(1.0)]
    assert out.count(math.inf) == 2
    assert stats.percentile(out, 50) == pytest.approx(1.0)
    assert stats.percentile(out, 90) == math.inf


# --------------------------------------------------------------- trace
def test_trace_reducer_on_the_scripted_trace():
    ev = scripted_trace.events()
    assert trace.device_planes(ev) == ["/device:TPU:0", "/device:TPU:1"]
    busy_s, window_s = trace.busy(ev)
    # chip 0: 3 x 100 + 3 x 2 us busy; chip 1: 3 x 110 + 3 x 2; window
    # is [0, 510)
    assert window_s == pytest.approx(510e-6)
    assert busy_s == pytest.approx((306e-6 + 336e-6) / 2)
    assert trace.ranked_modules(ev) == ["step(1)", "poke(2)"]
    assert trace.module_ms(ev, "step(1)") == pytest.approx(0.110)
    assert trace.module_ms(ev, "poke(2)") == pytest.approx(0.002)
    # chip 0 exposes its whole 20 us all-reduce; chip 1 only [40, 50)
    assert trace.exposed_collective_ms(ev, "step(1)") == pytest.approx(0.020)
    bd = trace.breakdown(ev)
    assert dict(bd["device_ops"])["fusion.2"] == pytest.approx(120e-6)
    assert dict(bd["device_ops"])["all-reduce.7"] == pytest.approx(60e-6)
    # chip 0 idle: [100,120) [122,140) [142,160) [162,200): 94 us inside
    # "dispatch"; [300,400): 100 us, of which "sample" covers 60
    assert dict(bd["idle_gaps"]) == {"dispatch": pytest.approx(94e-6),
                                     "sample": pytest.approx(100e-6)}


def test_recorded_traces_reduce():
    """Slices recorded on the v5e (chipbench/testdata): the reducer reads
    device planes, programs and a busy share between 0 and 1 from each."""
    names = [n for n in sorted(os.listdir(os.path.join(ROOT, "chipbench",
                                                        "testdata")))
             if n.endswith(".json")]
    assert names
    for n in names:
        with open(os.path.join(ROOT, "chipbench", "testdata", n)) as f:
            doc = json.load(f)
        ev = trace.unpack(doc["events"])
        assert trace.pack(ev) == doc["events"]
        assert len(trace.device_planes(ev)) == doc["expect"]["chips"]
        busy_s, window_s = trace.busy(ev)
        assert 0 < busy_s <= window_s
        assert busy_s / window_s == pytest.approx(
            doc["expect"]["busy_share"], rel=1e-6)
        ranked = trace.ranked_modules(ev, min_runs=1)
        assert ranked[0].startswith(doc["expect"]["top_module_prefix"])
        assert trace.module_ms(ev, ranked[0]) == pytest.approx(
            doc["expect"]["top_module_ms"], rel=1e-6)
        if "exposed_collective_ms" in doc["expect"]:
            assert trace.exposed_collective_ms(ev) == pytest.approx(
                doc["expect"]["exposed_collective_ms"], rel=1e-6)


def test_readers_return_none_without_anything_to_read():
    man = manifest.load()
    for w in man["workloads"]:
        cell = manifest.resolve(man, w["name"])
        for m in cell.per_layer:
            assert readers.read(m, {"device_kind": "TPU v5 lite"}) is None


def test_readers_on_scripted_observations():
    ring = [{"kind": "serve.decode.step", "ts_us": t, "window": w,
             "step_us": s}
            for t, w, s in [(0, 1, 10), (30, 1, 12), (130, 64, 50),
                            (160, 1, 14), (190, 1, 16)]]
    obs = {"ring": ring, "counters": {"serve.decode.tokens": 30,
                                      "serve.decode.iterations": 4},
           "series": {"ttft_s": [0.1, 0.2, 0.3]},
           "events": scripted_trace.events(), "device_kind": "TPU v5 lite",
           "chips": 2, "cost": {"train_step": {"flops": 197e12 * 2 * 55e-6,
                                               "bytes": 1.0}}}
    man = manifest.load()
    cells = {w["name"]: manifest.resolve(man, w["name"])
             for w in man["workloads"]}
    by_name = {m.name: m for c in cells.values() for m in c.per_layer}
    r = lambda name: readers.read(by_name[name], obs)  # noqa: E731
    assert r("sched.tokens_per_iter") == pytest.approx(7.5)
    assert r("sched.window_iter_share") == pytest.approx(20.0)
    assert r("engine.step_ms_p50") == pytest.approx(0.013)
    assert r("engine.window_ms_p50.chat") == pytest.approx(0.050)
    assert r("sched.iter_wall_ms_p50") == pytest.approx(0.030)
    assert r("sched.ttft_p50_ms") == pytest.approx(200.0)
    assert r("step.device_ms") == pytest.approx(0.110)
    # least time 55 us over 110 us measured
    assert r("train_step_roofline") == pytest.approx(50.0)
    assert r("allreduce.exposed_ms_per_step") == pytest.approx(0.020)


# --------------------------------------------------------------- costs
def test_costs_match_the_published_sizes():
    man = manifest.load()
    resnet = manifest.resolve(man, "resnet50-fit-1chip").config
    c = costs.resnet_train_step(resnet, 256)
    assert 3.8e9 < c["macs_per_sample"] < 4.3e9      # He et al.: 3.8 GFLOPs
    assert 25.0e6 < c["params"] < 25.7e6
    gpt = manifest.resolve(man, "cgpt1.3b-serve-chat-closed").config
    p = costs.gpt_params(gpt)
    assert 1.29e9 < p["layers"] + p["embedding"] < 1.33e9
    least, bound = costs.roofline(costs.gpt_step(gpt, 8, 1, 500),
                                  "TPU v5 lite")
    assert bound == "bandwidth" and 3e-3 < least < 5e-3
    with pytest.raises(KeyError):
        costs.peaks("TPU v9")


# ------------------------------------------------------------ manifest
def test_every_cell_resolves():
    man = manifest.load()
    for w in man["workloads"]:
        cell = manifest.resolve(man, w["name"])
        assert cell.config["kind"] in ("fit", "serve")
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        e2e = {m.name for m in cell.end_to_end}
        assert all(m.moves in e2e for m in cell.per_layer)


def test_new_files_and_entries_add_a_cell_without_an_edit(tmp_path):
    """A configuration, a traffic mix, a per-layer metric (one declared,
    one with a reader of its own) and a cell, added as new files plus
    new manifest entries; no file that was there changes."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    man = manifest.load()
    (root / "chipbench" / "configs" / "gpt-111m.json").write_text(
        json.dumps({"name": "gpt-111m", "kind": "serve", "arch": "gpt2",
                    "n_embd": 768}))
    (root / "chipbench" / "traffic" / "burst.json").write_text(
        json.dumps({"kind": "open_loop", "rate_rps": 5, "block": [[8, 8]]}))
    (root / "chipbench" / "layers" / "sched.migrations_per_iter.json") \
        .write_text(json.dumps({
            "reducer": "counter_ratio", "num": "serve.decode.migrations",
            "den": "serve.decode.iterations"}))
    (root / "chipbench" / "layers" / "sched.longest_iter_ms.py").write_text(
        "def read(obs):\n"
        "    recs = obs.get('ring') or []\n"
        "    return max(r['step_us'] for r in recs) / 1e3 if recs else None\n")
    man["configs"].append({"name": "gpt-111m", "source": "x", "reduced": [],
                           "file": "chipbench/configs/gpt-111m.json",
                           "why": "y"})
    man["workloads"].append({"name": "gpt-111m-burst", "config": "gpt-111m",
                             "traffic": "burst", "chips": 1, "why": "z"})
    man["end_to_end"][1]["workloads"].append("gpt-111m-burst")
    for name in ("sched.migrations_per_iter", "sched.longest_iter_ms"):
        man["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_counter", "layer": "DecodeScheduler",
            "moves": "serve_tokens_per_s", "workloads": ["gpt-111m-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    cell = manifest.resolve(manifest.load(root=str(root)), "gpt-111m-burst",
                            root=str(root))
    assert cell.config["n_embd"] == 768
    assert cell.traffic["rate_rps"] == 5
    assert [m.name for m in cell.per_layer] == [
        "sched.migrations_per_iter", "sched.longest_iter_ms"]
    obs = {"counters": {"serve.decode.migrations": 3,
                        "serve.decode.iterations": 6},
           "ring": [{"kind": "serve.decode.step", "step_us": 2500}]}
    assert readers.read(cell.per_layer[0], obs) == pytest.approx(0.5)
    assert readers.read(cell.per_layer[1], obs) == pytest.approx(2.5)
    assert readers.read(cell.per_layer[1], {}) is None
    after = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
             if p.is_file() and "__pycache__" not in str(p)}
    assert all(after[p] == data for p, data in before.items())
    with pytest.raises(manifest.ManifestError):
        manifest.resolve(manifest.load(root=str(root)), "no-such-cell",
                         root=str(root))
