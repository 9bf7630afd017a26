"""The benchmark's toy-size rehearsals (``benchmark/tests/test_rehearsal.py``,
``test_found_by_name.py`` and the readers' rehearsal of
``test_span_reduce.py``) inside the tier-1 gate — ``benchmark/tests`` is
collected by hand only: both drivers run traced and untraced, a seed fixes
the inputs, no result without the device, and files are found by the names
``BENCHMARK.json`` gives."""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_rehearsal as rehearsal  # noqa: E402
from benchmark.tests.conftest import toy_root  # noqa: E402,F401
from benchmark.tests.test_found_by_name import (  # noqa: E402,F401
    test_new_files_are_found_by_name,
)
from benchmark.tests.test_rehearsal import (  # noqa: E402,F401
    test_no_chip_exits_nonzero,
    test_same_seed_same_inputs,
    test_train_driver,
)
from benchmark.tests.test_span_reduce import (  # noqa: E402,F401
    test_new_readers_in_a_rehearsal,
)


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_driver(toy_root, capsys, monkeypatch, trace):
    """The case holds the line to the device count it ran on, which by hand
    is the CPU backend's one; ``tests/conftest.py`` gives the backend
    eight, so the run is shown the first alone."""
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: one)
    rehearsal.test_serve_driver(toy_root, capsys, trace)
