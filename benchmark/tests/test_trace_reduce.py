"""``lib/trace_reduce.py`` against a small recorded trace: the first 120 ms
of a traced window of ``mistral7b_chat_steady`` on a TPU v5e (my chip run,
PR 25), in the plain form, names cut to 96 characters."""
import gzip
import json
import os

import pytest

from benchmark.lib import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def plain():
    with gzip.open(os.path.join(HERE, "data", "trace_chat_120ms.json.gz"),
                   "rt") as f:
        return json.load(f)


def line(plain, plane, name):
    return next(l["events"] for p in plain["planes"] if p["name"] == plane
                for l in p["lines"] if l["name"] == name)


def test_window_is_the_benchmarks_own_annotations(plain):
    host = line(plain, "/host:CPU", "python3")
    assert {e[0] for e in host} == {"bench.step", "bench.submit"}
    red = tr.reduce(plain)
    lo = min(e[1] for e in host)
    hi = max(e[1] + e[2] for e in host)
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)


def test_busy_is_the_union_of_device_operations(plain):
    """Against a brute-force count of busy microseconds."""
    red = tr.reduce(plain)
    host = line(plain, "/host:CPU", "python3")
    lo = min(e[1] for e in host)
    hi = max(e[1] + e[2] for e in host)
    n = (hi - lo) // 1000 + 1
    busy = bytearray(n)
    for _, s, d in line(plain, "/device:TPU:0", "XLA Ops"):
        a, b = max(s, lo), min(s + d, hi)
        for us in range((a - lo) // 1000, max((b - lo) // 1000, 0)):
            busy[us] = 1
    assert red["busy_s"] == pytest.approx(sum(busy) / 1e6, rel=0.02)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["n_devices"] == 1


def test_module_times_by_name(plain):
    red = tr.reduce(plain)
    mods = line(plain, "/device:TPU:0", "XLA Modules")
    host = line(plain, "/host:CPU", "python3")
    lo = min(e[1] for e in host)
    hi = max(e[1] + e[2] for e in host)
    want = [d / 1e9 for name, s, d in mods
            if "serving_decode_steps" in name and s + d > lo and s < hi]
    got = tr.module_times(red, "serving_decode_steps")
    assert len(got) == len(want) > 0 and sum(got) == pytest.approx(sum(want))
    # a decode step of this configuration on a v5e: 7.25 GB of weights
    assert 0.012 < sum(got) / len(got) < 0.020
    assert tr.module_times(red, "no_such_program") == []


def test_breakdown_lists_are_short_and_named(plain):
    red = tr.reduce(plain)
    assert 0 < len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    assert all(len(name) <= 64 and sec > 0 for name, sec in red["device_ops"])
    assert all(name.startswith("bench.") or name == "host:none"
               for name, _ in red["idle_gaps"])
    secs = [s for _, s in red["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)


def test_op_name():
    assert tr.op_name("%fusion.7 = (pred[32]{0}, bf16[2,3]{1,0}) fusion(bf16[4]"
                      " %p), kind=kLoop") == "%fusion.7 fusion"
    assert tr.op_name("%copy.1 = f32[8]{0} copy(f32[8]{0} %x)") == "%copy.1 copy"


def test_a_run_cut_by_the_windows_edge_counts_by_its_part_inside():
    """Three 1 ms runs of one module; the window (the host annotation)
    opens in the middle of the first and closes with the third."""
    ms = 1_000_000
    mods = [["jit__step_fn(7)", 0, ms], ["jit__step_fn(7)", ms, ms],
            ["jit__step_fn(7)", 2 * ms, ms]]
    ops = [["%flash.1 = f32[8]{0} custom-call()", k * ms, ms // 2]
           for k in range(3)]
    red = tr.reduce({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.train_step", ms // 2, 5 * ms // 2]]}]}]})
    assert red["window_s"] == pytest.approx(2.5e-3)
    assert tr.module_runs(red, "_step_fn") == pytest.approx(2.5)
    assert tr.module_seconds(red, "_step_fn") == pytest.approx(2.5e-3)
    assert tr.module_times(red, "_step_fn") == [1e-3] * 3   # whole runs
    # the first run's operation ended before the window opened
    assert tr.op_seconds(red, "flash") == pytest.approx(1e-3)
    assert red["busy_s"] == pytest.approx(1e-3)


def test_no_device_plane_reads_nothing():
    red = tr.reduce({"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["bench.step", 0, 1000]]}]}]})
    assert red["busy_s"] == 0.0 and red["modules"] == {}
