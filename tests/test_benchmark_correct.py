"""What decides the benchmark's ``correct`` (``benchmark/tests/
test_correct.py``) inside the tier-1 gate — ``benchmark/tests`` is collected
by hand only: a sound run is correct, a planted fault (an altered token, an
unchanged state, half a batch) and the fp8 control are not, and the
reference's gradient is ``jax.grad``'s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests.conftest import toy_root  # noqa: E402,F401
from benchmark.tests.test_correct import (  # noqa: E402,F401
    test_reference_gradient_is_jax_grad,
    test_serving_control_fp8_reads_above_the_limit,
    test_serving_sound_run_is_correct,
    test_serving_token_altered_where_it_is_produced,
    test_training_control_fp8_comes_out_not_correct,
    test_training_faults_come_out_not_correct,
    test_training_sound_run_is_correct,
)
