"""CPU rehearsals of the benchmark at toy size.  Run by hand:

    python -m pytest benchmark/tests -q -p no:cacheprovider

They are not part of the repository's tier-1 tests (which collect
``tests/`` only).  Everything runs on the CPU backend with Pallas in
interpret mode; no number printed here is a device metric.
"""
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

S, T = ["toy_chat"], ["toy_steps"]


def _layer(name, unit, better, source, layer, moves, cells):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": cells}


def toy_benchmark():
    """A ``BENCHMARK.json`` of two toy cells with the real metric names."""
    e2e = lambda n, u, b, w: {"name": n, "unit": u, "better": b, "bound": 0.05,
                              "source": "host_clock", "workloads": w}
    return {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 30,
        "configs": [
            {"name": "toy_serve", "source": "toy", "reduced": [], "why": "toy",
             "file": "benchmark/configs/toy_serve.json"},
            {"name": "toy_train", "source": "toy", "reduced": [], "why": "toy",
             "file": "benchmark/configs/toy_train.json"}],
        "workloads": [
            {"name": "toy_chat", "config": "toy_serve", "traffic": "toy_chat",
             "chips": 1, "why": "toy"},
            {"name": "toy_steps", "config": "toy_train",
             "traffic": "toy_steps", "chips": 1, "why": "toy"}],
        "end_to_end": [
            e2e("ttft_p95_ms", "ms", "lower", S),
            e2e("tpot_p95_ms", "ms", "lower", S),
            e2e("serve_tokens_per_s", "tokens/s", "higher", S),
            e2e("train_tokens_per_s", "tokens/s", "higher", T),
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [
            _layer("gen_late_p95_ms", "ms", "lower", "host_clock",
                   "load generator", "ttft_p95_ms", S),
            _layer("decode_batch_mean", "slots", "higher", "program_counter",
                   "scheduler", "serve_tokens_per_s", S),
            _layer("decode_step_ms", "ms", "lower", "device_trace",
                   "model step", "tpot_p95_ms", S),
            _layer("train_mfu", "%", "higher", "device_trace", "train step",
                   "train_tokens_per_s", T)],
    }


@pytest.fixture
def toy_root(tmp_path, monkeypatch):
    """A checkout-shaped directory: the real drivers, generators and readers
    (linked), toy configuration and traffic files, a toy BENCHMARK.json."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    root = tmp_path / "root"
    bench = root / "benchmark"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    (bench / "layer_metrics").mkdir()
    for kind in ("drivers", "generators", "models"):
        os.symlink(os.path.join(BENCH, kind), bench / kind)
    for f in os.listdir(os.path.join(BENCH, "layer_metrics")):
        os.symlink(os.path.join(BENCH, "layer_metrics", f),
                   bench / "layer_metrics" / f)
    for f in ("toy_serve", "toy_train"):
        shutil.copy(os.path.join(HERE, "data", f + ".json"), bench / "configs")
    shutil.copy(os.path.join(HERE, "data", "toy_chat.json"), bench / "traffic")
    shutil.copy(os.path.join(BENCH, "traffic", "steps_back_to_back.json"),
                bench / "traffic" / "toy_steps.json")
    (root / "BENCHMARK.json").write_text(json.dumps(toy_benchmark()))
    return str(root)
