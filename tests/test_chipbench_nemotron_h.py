"""The benchmark's own tests of the Nemotron-H architecture
(chipbench/tests/test_nemotron_h.py: the interface, the configuration
against the catalog and its arithmetic, the costs by hand, the new
reader and the accepted readers of the two new kernel forms on a
synthetic obs, the traffic file against the issue's 32 pairs,
``make_params`` and the controls, a tree without the block, and the
cell's two CPU rehearsals at a tiny size) run in tier-1 as they stand;
the block against its reference is ``tests/test_nemotron_h.py``'s, in a
file of its own so that the two run on two workers."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.tests.test_nemotron_h import (  # noqa: E402,F401
    copy_with_nemotron,
    test_a_tree_without_the_block_fails_at_once,
    test_costs_against_a_count_by_hand,
    test_make_params_is_seeded_and_the_controls_are_switches,
    test_the_architecture_file_has_the_interface_and_builds_the_block,
    test_the_configuration_is_the_catalogs_but_for_what_reduced_lists,
    test_the_new_reader_and_the_new_kernel_forms_on_a_synthetic_obs,
    test_the_traffic_is_the_issues,
    test_tiny_nemotron_rehearses)
