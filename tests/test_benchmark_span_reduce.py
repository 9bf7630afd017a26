"""The benchmark's reduction of the program's names (``benchmark/lib/
span_reduce.py``) inside the tier-1 gate: ``benchmark/tests`` is collected
by hand only, and these cases hold the contract between the names the
program gives (``observability/trace.py``) and what the readers match."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests.test_span_reduce import (  # noqa: E402,F401
    recorded,
    test_a_program_without_names_reads_as_nothing,
    test_compiler_made_operations_inherit_their_consumers_path,
    test_events_are_clipped_to_the_benchmarks_window,
    test_host_spans_union_and_own_time,
    test_names_are_the_programs_vocabulary,
    test_no_trace_reads_as_nothing,
    test_readers_sum_their_scopes_per_run,
    test_recorded_decode_split_is_a_v5e_decode_step,
    test_recorded_host_spans_nest,
    test_recorded_scope_self_times_sum_to_the_modules_time,
    test_scope_is_the_innermost_name_of_the_path,
    test_scope_paths_are_read_from_the_xplanes_bytes,
    test_self_time_against_hand_sums,
    test_self_times_add_up_to_the_lines_union,
    test_ttft_legs_add_up_and_need_the_final_mark,
)
