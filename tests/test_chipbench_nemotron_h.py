"""The benchmark's own tests of the Nemotron-H architecture
(chipbench/tests/test_nemotron_h.py: the interface, the configuration
against the catalog and its arithmetic, the costs by hand, the new
reader and the accepted readers of the two new kernel forms on a
synthetic obs, the traffic file against the issue's 32 pairs,
``make_params`` and the controls, a tree without the block, and the
cell's two CPU rehearsals at a tiny size) run in tier-1 as they stand -
but for the traffic's, whose copy there pins PR 63's metric as the LAST
per-layer entry of ``BENCHMARK.json``, which every PR that appends one
moves, and is the next ``benchmark`` PR's to change (PERF.md section 7):
the one below is that test line for line, with that one assertion
replaced by the entry's place in front of what was appended since;
the block against its reference is ``tests/test_nemotron_h.py``'s, in a
file of its own so that the two run on two workers."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.tests.test_nemotron_h import (  # noqa: E402,F401
    BLOCK, GRANITE_CELL, NEW_METRICS, REAL_CELL, manifest, traffic_mod,
    copy_with_nemotron,
    test_a_tree_without_the_block_fails_at_once,
    test_costs_against_a_count_by_hand,
    test_make_params_is_seeded_and_the_controls_are_switches,
    test_the_architecture_file_has_the_interface_and_builds_the_block,
    test_the_configuration_is_the_catalogs_but_for_what_reduced_lists,
    test_the_new_reader_and_the_new_kernel_forms_on_a_synthetic_obs,
    test_tiny_nemotron_rehearses)


def test_the_traffic_is_the_issues():
    """``chipbench/tests/test_nemotron_h.py::test_the_traffic_is_the_
    issues`` line for line, but for the pin of the list's end."""
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    mix = cell.traffic
    assert cell.chips == 1 \
        and cell.config["name"] == "nemotron-3-nano-30b-a3b"
    assert [tuple(p) for p in mix["block"]] == BLOCK
    assert (mix["kind"], mix["clients"], mix["lead_in_blocks"],
            mix["trace_seconds"]) == ("closed_loop", 32, 1, 12)
    assert mix["clients"] == max(cell.config["ladder"]) \
        and "prefix" not in mix
    assert traffic_mod.block_totals(mix) == (32, 53500, 42976)
    assert max(p + n for p, n in BLOCK) == 6896 < cell.config["capacity"]
    assert not [p for p, _ in BLOCK if p % 256 == 0]
    assert sorted(p for p, _ in BLOCK)[-4:] == [6000] * 4
    # answers longer than prompts for most: the ratio turned round
    assert sum(n > p for p, n in BLOCK) == 21
    # nine tenths decode: the gap between tokens is what its callers
    # feel most (seven runs of PR 63 spread by 0.1 % of a half bound of 0.8)
    assert {m.name for m in cell.end_to_end} == {
        "serve_tokens_per_s", "serve_ttft_p90_ms", "serve_tpot_p95_ms",
        "setup_s"}
    # every per-layer metric of Granite Small's cell reads here too, but
    # the one whose declaration scales by Granite's 36 held experts and
    # the one whose list PR 54's test pins to that cell alone; and the
    # S = 1 read of the two attention layers' K/V
    granite = {m.name for m in manifest.resolve(man, GRANITE_CELL).per_layer}
    mine = {m.name for m in cell.per_layer}
    assert granite - mine == {"moe.held_load_imbalance",
                              "moe_window_roofline"}
    assert mine - granite == {"gqa_decode_roofline"}
    new = next(m for m in man["per_layer"] if m["name"] == NEW_METRICS[0])
    assert new == {"name": "moe.rows_per_touched_expert", "unit": "rows",
                   "better": "higher", "source": "program_counter",
                   "layer": "kernels", "moves": "serve_tokens_per_s",
                   "workloads": [GRANITE_CELL, REAL_CELL]}
    # that test's ``man["per_layer"][-1] == new``: the entry stands where
    # PR 63 appended it, and behind it only what later PRs appended
    names = [m["name"] for m in man["per_layer"]]
    assert names[names.index(new["name"]) + 1:] == [
        "engine.static_share_of_window_copies",
        "engine.static_share_of_window_copies.doc"]
    assert man["workloads"][-1]["name"] == REAL_CELL \
        and man["configs"][-1]["name"] == "nemotron-3-nano-30b-a3b"
    assert len(man["workloads"]) == 15 and len(man["configs"]) == 13
