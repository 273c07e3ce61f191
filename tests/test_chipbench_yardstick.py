"""Tier-1 guard of the yardstick itself (chipbench/README.md): the quick
cases of ``chipbench/tests/test_spans.py``, ``test_critical_path.py`` and
``test_yardstick.py`` run
here as they stand - the traffic generator's totals, nearest-rank
percentiles, tokens by timestamp, the trace reducer on a scripted trace
and on recorded v5e slices, every layer reader, the span readers, the
critical path of a decode iteration (``test_critical_path.py``),
``costs`` against the published sizes, and "new files and entries add a
cell without an edit". They are the measuring code every PR is judged
by. The CPU rehearsals (``test_rehearsal.py``, ``test_olmoe.py``) stay
by hand."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.tests.test_critical_path import (  # noqa: E402,F401
    test_a_skewed_device_plane_is_tied_back_by_its_markers,
    test_a_trace_without_the_new_spans_reads_as_nothing,
    test_an_impossible_order_reads_as_none_with_its_count,
    test_recorded_chat_slice_closes_the_account,
    test_the_eight_layer_files_read_ring_and_trace,
    test_three_iterations_by_hand,
    test_without_a_marker_the_crossing_latencies_read_as_none)
from chipbench.tests.test_spans import (  # noqa: E402,F401
    test_a_program_without_the_spans_reads_as_nothing,
    test_idle_ns_against_merged_busy_intervals,
    test_layer_files_read_the_scripted_observation,
    test_recorded_doc_slice_has_the_phases_inside_the_iterations,
    test_scripted_fetch_idle_and_idle_between_iterations)
from chipbench.tests.test_yardstick import (  # noqa: E402,F401
    test_chat_block_is_the_issue_s,
    test_costs_match_the_published_sizes,
    test_every_cell_resolves,
    test_generator_deals_whole_blocks_with_identical_totals,
    test_new_files_and_entries_add_a_cell_without_an_edit,
    test_open_loop_arrivals_and_prefixes,
    test_percentile_nearest_rank_and_unfinished_last,
    test_readers_on_scripted_observations,
    test_readers_return_none_without_anything_to_read,
    test_recorded_traces_reduce,
    test_same_seed_same_requests,
    test_tokens_by_timestamp_on_a_scripted_timeline,
    test_trace_reducer_on_the_scripted_trace,
    test_ttft_counts_window_submits_and_failures_as_largest)
