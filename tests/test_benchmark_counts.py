"""The benchmark's arithmetic (``benchmark/tests/test_flops.py``,
``test_percentile.py``, ``test_trace_reduce.py``) inside the tier-1 gate —
``benchmark/tests`` is collected by hand only: operation and byte counts
against hand counts, the percentile rule, and the reduction of a recorded
trace to busy time, module times and the window."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests.test_flops import (  # noqa: E402,F401
    test_decode_bytes,
    test_kv_and_attention,
    test_parameters,
    test_prefill_counts_the_causal_triangle,
    test_train_and_flash,
    test_unknown_device_is_an_error,
)
from benchmark.tests.test_percentile import (  # noqa: E402,F401
    test_interpolates_between_the_order_statistics_around_the_rank,
    test_small_samples,
)
from benchmark.tests.test_trace_reduce import (  # noqa: E402,F401
    plain,
    test_a_run_cut_by_the_windows_edge_counts_by_its_part_inside,
    test_breakdown_lists_are_short_and_named,
    test_busy_is_the_union_of_device_operations,
    test_module_times_by_name,
    test_no_device_plane_reads_nothing,
    test_op_name,
    test_window_is_the_benchmarks_own_annotations,
)
