"""The benchmark's own tests of the Granite 4.0-H Small architecture
(chipbench/tests/test_granite_moe_hybrid.py: the interface, the
configuration against the catalog, the costs by hand, the new readers on
a synthetic obs, ``make_params`` and the controls, and the cell's two
CPU rehearsals at a tiny size) run in tier-1 as they stand; the block
against its reference is ``tests/test_granite_moe_hybrid.py``'s, in a
file of its own so that the two run on two workers."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.tests.test_granite_moe_hybrid import (  # noqa: E402,F401
    copy_with_small, test_tiny_small_rehearses,
    test_costs_against_a_count_by_hand,
    test_every_new_reader_on_a_synthetic_obs,
    test_make_params_is_seeded_and_the_controls_are_switches,
    test_the_architecture_file_has_the_interface_and_builds_the_block,
    test_the_configuration_is_the_catalogs_but_for_what_reduced_lists,
    test_the_traffic_is_the_issues)
