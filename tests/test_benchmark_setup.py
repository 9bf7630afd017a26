"""The readers of the program's start-up record inside the tier-1 gate
(``benchmark/tests`` is collected by hand only): ``lib/setup_reduce.py``
and the eight ``setup_*`` metrics against a hand-made log, a program
without a record, and the toy rehearsal of both drivers reading the log
``paddle_tpu/observability/compilecache.py`` keeps."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests.conftest import toy_root  # noqa: E402,F401
from benchmark.tests.test_setup_reduce import (  # noqa: E402,F401
    test_a_program_without_the_log_reads_as_nothing,
    test_both_drivers_report_the_record,
    test_each_second_is_counted_once,
    test_reader_against_the_hand_made_log,
    test_reader_returns_nothing_without_a_record,
    test_setup_ends_at_its_last_compile,
    test_the_real_file_names_the_eight_for_every_cell,
)
