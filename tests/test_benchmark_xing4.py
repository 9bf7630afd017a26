"""The Xing4.0-29B-A4B cell's benchmark pieces inside the tier-1 gate
(``benchmark/tests`` is collected by hand only): the operation and byte
counts against hand counts, the new and the joined readers over a scoped
trace, the toy rehearsal of the ``serve_routed`` cell, and ``correct`` false
under the fp8 control and under each planted fault."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests.test_xing4 import (  # noqa: E402,F401
    test_altered_token_comes_out_not_correct,
    test_control_fp8_reads_above_the_limit,
    test_edited_statics_come_out_not_correct,
    test_hyper_connection_work,
    test_names_are_the_programs_scopes,
    test_parameters_at_the_published_sizes,
    test_planted_hyper_connection_fault_comes_out_not_correct,
    test_readers_read_the_hyper_connection_scopes,
    test_readers_return_nothing_for_a_program_without_the_scopes,
    test_routed_driver_runs_the_cell,
    test_selection_without_the_bias_comes_out_not_correct,
    test_swapped_expert_comes_out_not_correct,
    test_the_real_cell_reports_what_the_issue_names,
    test_unnormalised_gates_come_out_not_correct,
    xing_root,
)
