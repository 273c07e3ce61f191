"""The benchmark's own tests of the SDAR architecture
(chipbench/tests/test_sdar_moe.py: the interface with both optional
names, the configuration against the catalog, the costs by hand, every
new reader on a synthetic obs, the traffic file against the issue's
eight pairs, ``make_params`` and the controls, and the cell's CPU
rehearsal at a tiny size) run in tier-1 as they stand; the block against
its reference is ``tests/test_sdar_moe.py``'s, in a file of its own so
that the two run on two workers."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.tests.test_sdar_moe import (  # noqa: E402,F401
    copy_with_sdar, test_tiny_sdar_rehearses,
    test_costs_against_a_count_by_hand,
    test_every_new_reader_on_a_synthetic_obs,
    test_make_params_is_seeded_and_the_controls_are_switches,
    test_the_architecture_file_has_the_interface_with_both_optional_names,
    test_the_configuration_is_the_catalogs_but_for_what_reduced_lists,
    test_the_traffic_is_the_issues)
