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


def test_prefill_chunks_per_run_in_a_rehearsal(toy_root, capsys, monkeypatch):
    """The reader this repository's ``BENCHMARK.json`` entry names, in a
    traced toy run: chunks spent over runs of the prefill program, from the
    program's registry (1 <= chunks / runs <= ``prefill_budget``); a
    program without the runs counter — the parent — reads nothing and the
    line leaves the metric out."""
    import json

    from benchmark import run
    from benchmark.lib import harness
    from paddle_tpu.observability.metrics import get_registry

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: one)
    real = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    entry, = [e for e in real["per_layer"]
              if e["name"] == "prefill_chunks_per_run"]
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "scheduler", "tpot_p95_ms", "program_counter")
    path = os.path.join(toy_root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["per_layer"].append(dict(entry, workloads=["toy_chat"]))
    json.dump(bench, open(path, "w"))
    argv = ["--workload", "toy_chat", "--seed", "3000000019", "--seconds",
            "2", "--trace", "1"]
    run.main(argv, require_chip=False, root=toy_root)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert 1.0 <= line["metrics"]["prefill_chunks_per_run"]["value"] <= 2.0
    reader = harness.Files(toy_root).named("layer_metrics",
                                           "prefill_chunks_per_run")
    monkeypatch.setattr(get_registry(), "get", lambda name: None)
    assert reader.read({}) is None
