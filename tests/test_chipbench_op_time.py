"""Tier-1 guard of the operator table's readers in the benchmark
(``chipbench/op_time.py`` and the six layer files over it, PR 50):
``chipbench/tests/test_op_time.py`` runs here as it stands - a program
without the table reads as nothing, a scripted table to the digit, and
a slice recorded on the v5e through the program's own join."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.tests.test_op_time import (  # noqa: E402,F401
    test_a_program_without_the_table_reads_as_nothing,
    test_recorded_fit_slice_goes_through_the_programs_join,
    test_the_six_layer_files_read_a_scripted_table)
