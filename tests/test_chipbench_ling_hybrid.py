"""The benchmark's own tests of the Ling-3.0-flash architecture
(chipbench/tests/test_ling_hybrid.py, the cell's two CPU rehearsals at a
tiny size among them) run in tier-1 as they stand; the block against
its reference is ``tests/test_ling_hybrid.py``'s, so that the two run
on two workers (ROADMAP D22: no file over 300 s of the gate)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.tests.test_ling_hybrid import (  # noqa: E402,F401
    copy_with_ling, test_tiny_ling_rehearses,
    test_costs_against_a_count_by_hand,
    test_every_new_reader_on_a_synthetic_obs,
    test_make_params_draws_decays_that_span_the_bound,
    test_the_architecture_file_has_the_interface_and_builds_the_block,
    test_the_configuration_is_the_catalogs_but_for_what_reduced_lists,
    test_the_traffic_is_the_issues)
