"""Tier-1 guard of the benchmark's architecture seam (chipbench/README.md,
"The architecture interface"): the quick cases of
``chipbench/tests/test_archs.py`` run here as they stand - every
configuration names an architecture with its kind's interface, the two
moved architectures build, draw and count what the runners built, drew
and counted - and the same is asserted of ``archs/olmoe.py`` at a tiny
size. The slow cases (CPU rehearsals) stay by hand."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest  # noqa: E402
from chipbench.tests.test_archs import (  # noqa: E402,F401
    test_a_configuration_without_arch_is_an_error_that_names_the_key,
    test_an_architecture_that_lacks_a_name_is_refused,
    test_every_configuration_names_an_architecture_with_its_interface,
    test_gpt2_builds_the_symbol_the_runner_built,
    test_gpt2_draws_the_weights_the_runner_drew,
    test_resnet_pool_is_seeded_and_shaped,
    test_the_moved_costs_are_the_yardsticks)

# the quick cases of the benchmark's own tests of archs/afmoe.py run
# here as they stand (its CPU rehearsals stay by hand)
from chipbench.tests.test_afmoe import (  # noqa: E402,F401
    test_costs_against_a_count_by_hand as
    test_afmoe_costs_against_a_count_by_hand,
    test_the_configuration_is_the_catalogs_but_for_what_reduced_lists,
    test_the_three_controls_are_further_than_the_emulation)

CELL = "olmoe-1b-7b-serve-chat-closed"


@pytest.fixture(scope="module")
def olmoe():
    cell = manifest.resolve(manifest.load(), CELL)
    return cell, manifest.load_arch(cell)


def _tiny(cfg):
    with open(os.path.join(ROOT, "chipbench", "tests", "fixtures", "olmoe",
                           "configs", "tiny-olmoe.json")) as f:
        return json.load(f)


def test_olmoe_configuration_is_the_catalogs_but_for_the_depth(olmoe):
    cell, _arch = olmoe
    published = {       # architectures.jsonl, OLMoE-1B-7B-0125-Instruct
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    differs = [k for k, v in published.items() if cell.config[k] != v]
    assert differs == ["num_hidden_layers"] == cell.config["reduced"]
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "olmoe-1b-7b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cell.config["source"]
    assert cell.traffic["clients"] == 8 and cell.chips == 1


@pytest.mark.parametrize("step_len", [1, 8])
def test_olmoe_builds_the_programs_block(olmoe, step_len):
    cell, arch = olmoe
    cfg = _tiny(cell.config)
    sym = arch.decode_symbol(cfg, step_len)
    ops = [n.op for n in sym._topo_nodes() if not n.is_variable]
    L = cfg["num_hidden_layers"]
    assert ops.count("MoEFFN") == L and ops.count("attention_decode") == L
    assert ops.count("RMSNorm") == 4 * L + 1 and "LayerNorm" not in ops
    args = sym.list_arguments()
    assert "pos_ids" not in args and "lm_head_weight" in args
    assert not [a for a in args if a.endswith(("_bias", "_beta"))]
    assert arch.data_shapes(cfg, 4, step_len) == {"data": (4, step_len)}
    with pytest.raises(SystemExit, match="published block"):
        arch.decode_symbol(dict(cfg, attention_bias=True), step_len)


def test_olmoe_draws_bfloat16_parameters_from_the_seed(olmoe):
    cell, arch = olmoe
    cfg = _tiny(cell.config)
    sym = arch.decode_symbol(cfg, 1)
    shapes = arch.data_shapes(cfg, 4, 1)
    got = arch.make_params(sym, shapes, 2**31 + 28, cfg)
    again = arch.make_params(sym, shapes, 2**31 + 28, cfg)
    assert sorted(got) == sorted(n for n in sym.list_arguments()
                                 if n not in shapes)
    assert {str(v.dtype) for v in got.values()} == {"bfloat16"}
    assert got["lm_l0_moe_gate_weight"].shape == (8, 64, 32)
    assert got["lm_l1_moe_down_weight"].shape == (8, 32, 64)
    assert got["lm_l0_qkv_weight"].shape == (192, 64)
    assert (np.asarray(got["lm_l0_q_norm_gamma"], np.float32) == 1).all()
    assert all(np.array_equal(got[n], again[n]) for n in got)
    other = arch.make_params(sym, shapes, 7, cfg)
    assert not np.array_equal(got["lm_head_weight"], other["lm_head_weight"])


def test_olmoe_counts_the_experts_touched(olmoe):
    cell, arch = olmoe
    cfg = cell.config
    expert = 3 * 2048 * 1024 * 2
    assert arch.moe_expert_bytes(cfg) == expert == 12_582_912
    touched = 64 * (1 - (7 / 8) ** 8)
    assert arch.experts_touched(cfg, 8) == pytest.approx(touched)
    assert touched == pytest.approx(42.0, abs=0.1)
    assert arch.experts_touched(cfg, 512) == pytest.approx(64.0)
    got = arch.costs(cfg, 8, 64, 300.0)
    assert sorted(got) == ["decode_step", "moe_expert", "window_step"]
    assert got["moe_expert"]["bytes"] == expert
    d, L, V = 2048, 8, 50304
    dense = 4 * d * d + 64 * d
    kv_row = 2 * d * L * 2
    want = ((L * dense + V * d) * 2 + L * touched * expert + 8 * d * 2
            + 8 * 300.0 * kv_row + 8 * kv_row + 8 * V * 4)
    assert got["decode_step"]["bytes"] == pytest.approx(want)
    # nine tenths of a decode step's bytes are the experts it chose
    assert 0.85 < L * touched * expert / want < 0.9
    flops = 2 * 8 * (L * (dense + 8 * 3 * d * 1024) + V * d) \
        + 4 * 8 * 300.5 * d * L
    assert got["decode_step"]["flops"] == pytest.approx(flops)
    assert got["window_step"]["bytes"] > got["decode_step"]["bytes"]
    assert got["window_step"]["experts_touched_per_layer"] == \
        pytest.approx(64.0)


def test_olmoe_reference_is_independent_of_the_program():
    with open(os.path.join(ROOT, "chipbench", "reference", "olmoe.py")) as f:
        text = f.read()
    assert "mxnet_tpu" not in text.split('"""', 2)[2]     # prose may name it
    assert 'default_matmul_precision("highest")' in text
