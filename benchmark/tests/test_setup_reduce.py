"""``lib/setup_reduce.py`` and the eight ``setup_*`` readers: a hand-made
start-up log against hand sums (what lies after set-up's last compile is
left out, each second is counted once), a program without a record, and
the toy rehearsal of both drivers reading the program's own log."""
import json
import math
import os
import re

import pytest

from benchmark import run
from benchmark.lib import harness, setup_reduce

READERS = ("setup_import_s", "setup_construct_s", "setup_trace_s",
           "setup_lower_s", "setup_load_s", "setup_first_call_self_s",
           "setup_programs", "setup_gc_s")


def phase(name, t0, t1, tid=1):
    return {"stage": "phase", "name": name, "t_start": t0, "t_end": t1,
            "parent": None, "tid": tid}


def stage(stage_, t0, t1, program="-", fun_name="jit(f)", tid=1):
    return {"stage": stage_, "cache": "-" if program == "-" else "c",
            "program": program, "fun_name": fun_name, "t_start": t0,
            "t_end": t1, "parent": None, "tid": tid}


def hand_log():
    """The import (2 s); an engine's construction (3 s) holding its weights
    (1 s, a helper's 0.3 s load with 0.1 s of retrieval inside), its cache
    (0.5 s) and a 0.2 s collection; one program's first call (3 s): 1 s of
    trace with a helper's 0.2 s load inside it, 0.5 s of lowering, 1 s of
    load; a 0.5 s load on another thread at the same time.  Then the
    window (traced from t = 20) with a collection inside it, and the
    reference's compiles after it.  In the order the intervals ended."""
    return [
        phase("import", 0.0, 2.0),
        stage("cache_retrieval", 3.25, 3.35),
        stage("load", 3.2, 3.5),
        phase("serving.init.params", 3.1, 4.1),
        phase("serving.init.cache", 4.5, 5.0),
        phase("host.gc", 5.2, 5.4),
        phase("serving.init", 3.0, 6.0),
        stage("load", 7.4, 7.6),
        stage("trace", 7.1, 8.1, "serving_decode_steps", "impl"),
        stage("lower", 8.2, 8.7, "serving_decode_steps"),
        stage("cache_retrieval", 8.9, 9.1, "serving_decode_steps"),
        stage("load", 9.0, 9.5, tid=2),
        stage("load", 8.8, 9.8, "serving_decode_steps"),
        stage("first_call", 7.0, 10.0, "serving_decode_steps", ""),
        phase("host.gc", 15.0, 15.5),
        stage("trace", 30.0, 31.0, fun_name="reference"),
        stage("load", 31.0, 33.0, fun_name="jit(reference)"),
    ]


BY_HAND = {
    "setup_import_s": 2.0,
    # init 3.0 - (1.0 + 0.5 + 0.2); params 1.0 - 0.3; cache 0.5
    "setup_construct_s": 1.3 + 0.7 + 0.5,
    "setup_trace_s": 1.0 - 0.2,
    "setup_lower_s": 0.5,
    "setup_load_s": 0.3 + 0.2 + 1.0 + 0.5,
    "setup_first_call_self_s": 3.0 - (1.0 + 0.5 + 1.0),
    "setup_programs": 4.0,
    "setup_gc_s": 0.2,
}


def reader(name):
    return harness.Files().named("layer_metrics", name)


@pytest.mark.parametrize("name", READERS)
def test_reader_against_the_hand_made_log(monkeypatch, name):
    monkeypatch.setattr(setup_reduce, "entries", hand_log)
    ctx = {"record": {"traced": (20.0, 24.0)}}
    assert reader(name).read(ctx) == pytest.approx(BY_HAND[name], abs=1e-9)


def test_each_second_is_counted_once(monkeypatch):
    """The seconds by kind add up to the union of set-up's intervals (on
    one thread) — nothing twice — and the retrieval is beside, not in."""
    monkeypatch.setattr(setup_reduce, "entries", hand_log)
    t = setup_reduce.table({"record": {"traced": (20.0, 24.0)}})
    assert sum(t["seconds"].values()) == pytest.approx(2.0 + 3.0 + 3.0 + 0.5)
    assert t["retrieval_s"] == pytest.approx(0.1 + 0.2)
    assert t["rows"][0][:2] == ("import", "phase")
    assert ("c/serving_decode_steps", "load", pytest.approx(1.0), 1) \
        in t["rows"]


def test_setup_ends_at_its_last_compile(monkeypatch):
    log = hand_log()
    kept = setup_reduce.of_setup(log, 20.0)
    assert max(e["t_end"] for e in kept) == 10.0      # the first call's end
    assert all(e["t_start"] <= 9.8 for e in kept)
    assert setup_reduce.of_setup(log, 3.0) == []
    # a window traced from later on sees the same set-up
    assert setup_reduce.of_setup(log, 29.0) == kept


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_nothing_without_a_record(monkeypatch, name):
    """The parent of PR 37 keeps no log; a run without a traced sub-window
    has no boundary: the metric is left out, never 0."""
    monkeypatch.setattr(setup_reduce, "entries", lambda: None)
    assert reader(name).read({"record": {"traced": (20.0, 24.0)}}) is None
    monkeypatch.setattr(setup_reduce, "entries", hand_log)
    assert reader(name).read({"record": {"traced": None}}) is None
    assert reader(name).read({"record": {"traced": (1.0, 2.0)}}) is None


def test_a_program_without_the_log_reads_as_nothing(monkeypatch):
    from paddle_tpu.observability import compilecache

    monkeypatch.delattr(compilecache, "startup")
    assert setup_reduce.entries() is None


def with_setup_metrics(toy_root, cells):
    """The eight entries added to the test's own copy of the toy
    ``BENCHMARK.json``, as the real file gained them."""
    path = os.path.join(toy_root, "BENCHMARK.json")
    real = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    b = harness.load_json(path)
    for e in real["per_layer"]:
        if e["name"] in READERS:
            b["per_layer"].append(dict(e, workloads=cells))
    with open(path, "w") as f:
        json.dump(b, f)


@pytest.mark.parametrize("workload,seed", [("toy_chat", "3000000019"),
                                           ("toy_steps", "2147483659")])
@pytest.mark.parametrize("trace", [1, 0])
def test_both_drivers_report_the_record(toy_root, capsys, monkeypatch,
                                        workload, seed, trace):
    """A traced run of either driver prints all eight, finite, from the
    program's own log; ``setup_programs`` is the count the harness made
    from outside (``backend_compiles`` of the ``setup`` line); an untraced
    run prints none.  The log starts empty: this process has compiled for
    other tests."""
    from paddle_tpu.observability import compilecache

    monkeypatch.setattr(compilecache, "startup", compilecache.StartupLog())
    with_setup_metrics(toy_root, ["toy_chat", "toy_steps"])
    run.main(["--workload", workload, "--seed", seed, "--seconds", "2",
              "--trace", str(trace)], require_chip=False, root=toy_root)
    io = capsys.readouterr()
    line = json.loads(io.out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    got = {k: v["value"] for k, v in line["metrics"].items() if k in READERS}
    if not trace:
        assert got == {}
        return
    assert set(got) == set(READERS)
    assert all(math.isfinite(v) and v >= 0 for v in got.values())
    counted = int(re.search(r"^\[setup\] .*backend_compiles=(\d+)", io.err,
                            re.M).group(1))
    assert got["setup_programs"] == counted > 0
    assert got["setup_trace_s"] > 0 and got["setup_load_s"] > 0
    assert got["setup_construct_s"] > 0
    assert line["metrics"]["setup_programs"]["unit"] == "programs"


def test_the_real_file_names_the_eight_for_every_cell():
    b = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in b["workloads"]]
    mine = [e for e in b["per_layer"] if e["name"] in READERS]
    assert [e["name"] for e in mine] == list(READERS)
    assert [e["name"] for e in b["per_layer"][-8:]] == list(READERS)
    for e in mine:
        assert e["moves"] == "setup_s" and e["better"] == "lower"
        assert e["workloads"] == cells
        assert e["layer"] in ("service", "model step")
