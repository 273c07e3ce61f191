"""The benchmark's own quick tests of the three latent-attention
architectures (chipbench/tests/test_glm_dsa.py, test_axk1.py,
test_xing4.py) run in tier-1 as they stand, each under its
architecture's name (their CPU rehearsals stay by hand); a file of its
own so that they are a worker's and not the blocks' (ROADMAP D22, D27)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.tests.test_glm_dsa import (  # noqa: E402,F401
    test_both_controls_are_further_than_the_emulation as
    test_glm_dsa_both_controls_are_further_than_the_emulation,
    test_costs_against_a_count_by_hand as
    test_glm_dsa_costs_against_a_count_by_hand)
from chipbench.tests.test_axk1 import (  # noqa: E402,F401
    test_costs_against_a_count_by_hand as
    test_axk1_costs_against_a_count_by_hand,
    test_every_new_reader_on_a_scripted_trace as
    test_axk1_every_new_reader_on_a_scripted_trace,
    test_the_architecture_file_has_the_interface_and_builds_the_block as
    test_axk1_the_architecture_file_has_the_interface_and_builds_the_block,
    test_the_configuration_is_the_catalogs_but_for_what_reduced_lists as
    test_axk1_the_configuration_is_the_catalogs_but_for_what_reduced_lists,
    test_the_controls_are_further_than_the_emulation as
    test_axk1_the_controls_are_further_than_the_emulation,
    test_the_traffic_is_the_issues_and_shares_three_documents as
    test_axk1_the_traffic_is_the_issues_and_shares_three_documents)
from chipbench.tests.test_xing4 import (  # noqa: E402,F401
    test_costs_against_a_count_by_hand as
    test_xing4_costs_against_a_count_by_hand,
    test_every_new_reader_on_a_scripted_trace as
    test_xing4_every_new_reader_on_a_scripted_trace,
    test_the_architecture_file_has_the_interface_and_builds_the_block as
    test_xing4_the_architecture_file_has_the_interface_and_builds_the_block,
    test_the_configuration_is_the_catalogs_but_for_what_reduced_lists as
    test_xing4_the_configuration_is_the_catalogs_but_for_what_reduced_lists,
    test_the_controls_are_further_than_the_emulation as
    test_xing4_the_controls_are_further_than_the_emulation,
    test_the_traffic_is_the_issues as
    test_xing4_the_traffic_is_the_issues)
