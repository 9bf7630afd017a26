"""A configuration, a traffic mix, a generator, an architecture and a
per-layer metric are each added as new files plus new entries in
``BENCHMARK.json`` — no file that is there is edited."""
import json
import os

from benchmark import run


def own_dir(bench, kind):
    """The fixture links the real directory; make it one this test may add
    a file to."""
    path = os.path.join(bench, kind)
    real = os.path.realpath(path)
    os.unlink(path)
    os.mkdir(path)
    for name in os.listdir(real):
        os.symlink(os.path.join(real, name), os.path.join(path, name))
    return path


def test_new_files_are_found_by_name(toy_root, capsys):
    bench = os.path.join(toy_root, "benchmark")
    # a new configuration: its file, named by its entry
    cfg = json.load(open(os.path.join(bench, "configs", "toy_serve.json")))
    cfg["engine"] = {"batch_size": 2, "max_len": 96}
    cfg["model"] = "llama_too"
    json.dump(cfg, open(os.path.join(bench, "configs", "toy_two.json"), "w"))
    # a new architecture file, named by the configuration's ``model``
    with open(os.path.join(own_dir(bench, "models"), "llama_too.py"),
              "w") as f:
        f.write("from benchmark.models.llama import *  # noqa: F401,F403\n")
    # a new generator: a file under generators/
    gen_dir = own_dir(bench, "generators")
    with open(os.path.join(gen_dir, "every_100ms.py"), "w") as f:
        f.write(
            "import numpy as np\n"
            "def extremes(traffic):\n"
            "    return (16, 16), (4, 4)\n"
            "def schedule(traffic, seed, seconds, vocab_size):\n"
            "    rng = np.random.default_rng(seed)\n"
            "    return [(0.1 * i, rng.integers(1, vocab_size, 16)"
            ".astype(np.int32), 4) for i in range(int(seconds * 10))]\n")
    # a new traffic mix naming the new generator
    json.dump({"generator": "every_100ms", "trace_seconds": 0.5},
              open(os.path.join(bench, "traffic", "ticks.json"), "w"))
    # a new per-layer metric: a reader of its own
    with open(os.path.join(bench, "layer_metrics", "requests_seen.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    return float(len(ctx['record']['requests']))\n")
    path = os.path.join(toy_root, "BENCHMARK.json")
    b = json.load(open(path))
    b["configs"].append({"name": "toy_two", "source": "toy", "reduced": [],
                         "file": "benchmark/configs/toy_two.json",
                         "why": "toy"})
    b["workloads"].append({"name": "toy_two.ticks", "config": "toy_two",
                           "traffic": "ticks", "chips": 1, "why": "toy"})
    for e in b["end_to_end"]:
        if "workloads" in e and "toy_chat" in e["workloads"]:
            e["workloads"].append("toy_two.ticks")
    b["per_layer"].append({
        "name": "requests_seen", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "serve_tokens_per_s", "workloads": ["toy_two.ticks"]})
    json.dump(b, open(path, "w"))

    run.main(["--workload", "toy_two.ticks", "--seed", "7", "--seconds", "1",
              "--trace", "1"], require_chip=False, root=toy_root)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["attempted"] == 10
    assert line["metrics"] == {"requests_seen": {"value": 10.0,
                                                 "unit": "requests"}}
