"""The Falcon-H1 cell's benchmark pieces inside the tier-1 gate
(``benchmark/tests`` is collected by hand only): the operation and byte
counts against hand counts, the new readers over a scoped trace, the toy
rehearsal of the cell, and ``correct`` false under the fp8 control and under
a skipped state reset."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests.test_falconh1 import (  # noqa: E402,F401
    falcon_root,
    test_control_fp8_reads_above_the_limit,
    test_names_are_the_programs_state_scopes,
    test_parameters_at_the_published_sizes,
    test_readers_read_the_state_space_scopes,
    test_readers_return_nothing_for_a_program_without_the_scopes,
    test_recurrence_operations,
    test_serve_driver_runs_the_cell,
    test_skipped_state_reset_comes_out_not_correct,
    test_state_bytes,
)
