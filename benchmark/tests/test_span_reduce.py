"""``lib/span_reduce.py``: self time against hand sums on a toy plane with a
nested ``%while``, scope paths, the host spans' union, a recorded chip
trace of the scoped program, and readers that find nothing."""
import gzip
import json
import os

import pytest

from benchmark.lib import span_reduce as sr
from benchmark.lib import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = sr.NAMES


def toy_plain():
    """One device, one run of a decode module of 100 us inside a 120 us
    window.  A ``%while`` of 60 us holds three body operations (10 + 20 +
    15 us) and 15 us of its own; beside it 10 us of ``mlp``, 8 us of an
    unnamed copy and two 1 us halves of an async pair that overlap."""
    ops = [
        ["%fusion.1 fusion", 1000, 10_000, "jit(f)/decode.steps/while/body/mlp/dot_general"],
        ["%while.7 while", 12_000, 60_000,
         "jit(f)/decode.steps/while/body/jit(decode_attention)/attn.core/attn.core.chunks/while"],
        ["%fusion.2 fusion", 13_000, 10_000,
         "jit(f)/decode.steps/while/body/jit(decode_attention)/attn.core/attn.core.chunks/while/body/dot_general"],
        ["%fusion.3 fusion", 25_000, 20_000,
         "jit(f)/decode.steps/while/body/jit(decode_attention)/attn.core/attn.core.chunks/while/body/exp"],
        ["%fusion.4 fusion", 50_000, 15_000,
         "jit(f)/decode.steps/while/body/jit(decode_attention)/attn.core/attn.core.chunks/while/body/jit(norm)/mul"],
        ["%copy.5 copy", 75_000, 8_000, ""],
        ["%copy-start.1 copy-start", 90_000, 1_000, ""],
        ["%copy-done.1 copy-done", 90_500, 1_000, ""],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules",
             "events": [["jit__serving_decode_steps_impl(1)", 0, 100_000, ""]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ["bench.step", 0, 120_000, {}],
                ["serving.step", 2_000, 100_000, {"step": 4}],
                ["serving.dispatch", 3_000, 5_000, {"step": 4, "n_live": 3}],
                ["serving.drain", 10_000, 90_000, {"step": 4}],
                ["serving.drain.wait", 11_000, 70_000, {"step": 4}],
                ["serving.emit", 82_000, 17_000, {"step": 4}],
                ["serving.submit", 105_000, 3_000, {"rid": 9}]]}]},
    ]}


def test_self_time_against_hand_sums():
    red = sr.reduce(toy_plain(), NAMES)
    table = red["self_s"]["jit__serving_decode_steps_impl"]
    us = {k: round(v * 1e6, 6) for k, v in table.items()}
    # the loop keeps 60 - 45 = 15 us of its own; its body's operations are
    # charged to themselves (jit(norm) is a function, not the norm scope)
    assert us == {"mlp": 10.0, "attn.core.chunks": 60.0, "": 10.0}
    assert red["module_runs"] == {"jit__serving_decode_steps_impl": 1.0}
    pairs = dict((ev[0], ns) for ev, ns in sr.self_times(
        tr._line(toy_plain()["planes"][0], "XLA Ops")))
    assert pairs["%while.7 while"] == 15_000
    assert pairs["%fusion.3 fusion"] == 20_000
    # an operation that overlaps its predecessor without nesting in it is
    # charged whole
    assert pairs["%copy-done.1 copy-done"] == 1_000


def test_self_times_add_up_to_the_lines_union():
    ops = tr._line(toy_plain()["planes"][0], "XLA Ops")
    union = sum(e - s for s, e in tr._union(
        [(ev[1], ev[1] + ev[2]) for ev in ops]))
    total = sum(ns for _, ns in sr.self_times(ops))
    # the one overlap (0.5 us of the async pair) is counted twice
    assert total == union + 500


def test_readers_sum_their_scopes_per_run(monkeypatch):
    red = sr.reduce(toy_plain(), NAMES)
    monkeypatch.setattr(sr, "for_run", lambda ctx: red)
    assert sr.ms_per_run({}, "serving_decode_steps",
                         ("attn.core", "attn.core.chunks",
                          "attn.kv_write")) == pytest.approx(0.060)
    assert sr.ms_per_run({}, "serving_decode_steps", ("mlp", "lm_head")) \
        == pytest.approx(0.010)
    # a scope nothing ran under, a module that did not run: nothing, not 0
    assert sr.ms_per_run({}, "serving_decode_steps", ("loss",)) is None
    assert sr.ms_per_run({}, "_step_fn", ("mlp",)) is None
    assert sr.coverage_pct({}) == pytest.approx(100 * 70 / 80)
    assert sr.recompute_ms_per_run({}, "serving_decode_steps") is None


def test_host_spans_union_and_own_time(monkeypatch):
    red = sr.reduce(toy_plain(), NAMES)
    assert red["window_s"] == pytest.approx(120e-6)
    assert red["host_s"]["serving.drain.wait"] == pytest.approx(70e-6)
    assert red["host_s"]["serving.step"] == pytest.approx(100e-6)
    # inside step or submit (103 us) and not inside the blocking fetch
    assert red["host_own_s"] == pytest.approx(33e-6)
    monkeypatch.setattr(sr, "for_run", lambda ctx: red)
    assert sr.host_own_pct({}) == pytest.approx(100 * 33 / 120)


def test_events_are_clipped_to_the_benchmarks_window():
    plain = toy_plain()
    plain["planes"][1]["lines"][0]["events"][0] = ["bench.step", 20_000,
                                                   60_000, {}]
    red = sr.reduce(plain, NAMES)
    table = red["self_s"]["jit__serving_decode_steps_impl"]
    # window 20..80 us: the loop's last 52 us, fusion.2's last 3, all of
    # fusion.3 and fusion.4, 5 us of the copy; the mlp fusion lies outside
    assert "mlp" not in table
    assert table["attn.core.chunks"] == pytest.approx(52e-6)
    assert table[""] == pytest.approx(5e-6)
    assert red["module_runs"]["jit__serving_decode_steps_impl"] \
        == pytest.approx(0.6)
    assert red["host_s"]["serving.drain.wait"] == pytest.approx(60e-6)


@pytest.mark.parametrize("path,scope", [
    ("jit(_step_fn)/transpose(jvp(attn.core))/dot_general", "attn.core"),
    ("jit(_step_fn)/jvp(jit(norm))/mul", ""),
    ("jit(f)/checkpoint/rematted_computation/mlp/jit(silu)/exp", "mlp"),
    ("jit(f)/loss/while/body/checkpoint/lm_head/dot_general", "lm_head"),
    ("jit(f)/attn.core/attn.core.chunks/while", "attn.core.chunks"),
    ("jit(f)/optimizer/jit(norm)/sqrt", "optimizer"),
    ("jit(f)/attn.corex/mul", ""),
    ("", ""),
])
def test_scope_is_the_innermost_name_of_the_path(path, scope):
    assert sr.scope_of(path, frozenset(NAMES)) == scope


def test_a_program_without_names_reads_as_nothing(monkeypatch):
    """The parent of PR 26: no scope in any path, no span on the host."""
    plain = toy_plain()
    for ev in plain["planes"][0]["lines"][1]["events"]:
        ev[3] = ""
    plain["planes"][1]["lines"][0]["events"] = [["bench.step", 0, 120_000,
                                                 {}]]
    red = sr.reduce(plain, NAMES)
    monkeypatch.setattr(sr, "for_run", lambda ctx: red)
    assert sr.ms_per_run({}, "serving_decode_steps", ("mlp",)) is None
    assert sr.coverage_pct({}) is None
    assert sr.host_own_pct({}) is None


def test_no_trace_reads_as_nothing(monkeypatch, tmp_path):
    monkeypatch.setattr(sr.harness, "ROOT", str(tmp_path))
    sr._cache.clear()
    assert sr.newest_xplane() is None
    assert sr.for_run({}) is None
    assert sr.ms_per_run({}, "serving_decode_steps", ("mlp",)) is None
    assert sr.coverage_pct({}) is None and sr.host_own_pct({}) is None
    assert sr.recompute_ms_per_run({}, "_step_fn") is None


def test_scope_paths_are_read_from_the_xplanes_bytes():
    """``data/tiny_scoped.xplane.pb``: an XSpace written with tsl's own
    ``xplane_pb2`` / ``hlo_pb2``.  Its device plane has ONE HLO line that
    stands in two programs (a path in each, in the event metadata's
    ``tf_op`` stat beside other stats), a copy without a path whose only
    consumer chain — copy -> tuple -> while — exists in the program's HLO
    module alone (``/host:metadata``), and a host plane with a ``tf_op`` of
    its own that must be left alone."""
    program = 14168201891686980001
    got = sr.op_paths(os.path.join(HERE, "data", "tiny_scoped.xplane.pb"))
    assert got == {
        "%fusion.9 = f32[] fusion(f32[] %param.1)": {
            program: "jit(f)/embed/gather", 5: "jit(g)/norm/mul"},
        "%while.5 = (f32[4]) while((f32[4]) %tuple.4), body=%b": {
            program: "jit(f)/decode.steps/while"},
        "%copy.3 = f32[4]{0} copy(f32[4]{0} %param.1)": {
            program: "~jit(f)/decode.steps/while"}}
    assert sr._program_of(f"jit__step_fn({program})") == program
    assert sr._program_of("jit_add") == 0


def test_compiler_made_operations_inherit_their_consumers_path():
    """A weight's async slice, its wait, the concat and the re-layout copy
    have no op_name; the loop that consumes the copy has: all four are
    named after it (the nearest named consumer), marked inherited.  An
    operation nothing named consumes takes its producer's path."""
    nodes = {
        "%slice-start.1": ["", ["%params.wk"]],
        "%slice-done.1": ["", ["%slice-start.1"]],
        "%custom-call.2": ["", ["%slice-done.1"]],
        "%copy.3": ["", ["%custom-call.2"]],
        "%fusion.9": ["jit(f)/embed/gather", ["%cur"]],
        "%while.4": ["jit(f)/decode.steps/while", ["%copy.3", "%fusion.9"]],
        "%copy-done.7": ["", ["%while.4"]],
        "%lonely.8": ["", []],
    }
    got = sr._inherit(nodes)
    assert got == {k: "~jit(f)/decode.steps/while" for k in (
        "%slice-start.1", "%slice-done.1", "%custom-call.2", "%copy.3",
        "%copy-done.7")}
    assert sr.scope_of(got["%copy.3"], frozenset(NAMES)) == "decode.steps"
    plain = toy_plain()
    plain["planes"][0]["lines"][1]["events"][5][3] = got["%copy.3"]
    red = sr.reduce(plain, NAMES)
    module = "jit__serving_decode_steps_impl"
    assert red["self_s"][module]["decode.steps"] == pytest.approx(8e-6)
    assert red["inherited_s"][module] == {"decode.steps": pytest.approx(8e-6)}


@pytest.fixture(scope="module")
def recorded():
    """The first 120 ms of a traced window of ``mistral7b_chat_steady`` on a
    TPU v5e with the scoped program (my chip run, PR 26), in this module's
    plain form (``tools/xplane_probe.py --record``), names cut to 96."""
    with gzip.open(os.path.join(HERE, "data",
                                "trace_chat_scoped_120ms.json.gz"), "rt") as f:
        return json.load(f)


def _without_extras(plain):
    return {"planes": [{"name": p["name"], "lines": [
        {"name": line["name"], "events": [ev[:3] for ev in line["events"]]}
        for line in p["lines"]]} for p in plain["planes"]]}


@pytest.mark.parametrize("module", ["jit__serving_decode_steps_impl",
                                    "jit__serving_prefill_chunk_impl"])
def test_recorded_scope_self_times_sum_to_the_modules_time(recorded, module):
    """Closure: scopes plus the unnamed remainder are the module's device
    time, as ``trace_reduce`` reads it from the XLA Modules line."""
    red = sr.reduce(recorded, NAMES)
    old = tr.reduce(_without_extras(recorded))
    assert red["window_s"] == pytest.approx(old["window_s"])
    assert red["module_runs"][module] == pytest.approx(
        old["module_runs"][module])
    total = sum(red["self_s"][module].values())
    assert total == pytest.approx(old["module_s"][module], rel=0.01)
    table = red["self_s"][module]
    assert max(table, key=table.get) == "mlp"
    assert {"attn.core.chunks", "attn.kv_write", "attn.qkv", "attn.out",
            "lm_head", "norm", "embed", "sample"} <= set(table)
    assert ("decode.steps" in table) == ("decode" in module)


def test_recorded_decode_split_is_a_v5e_decode_step(recorded, monkeypatch):
    red = sr.reduce(recorded, NAMES)
    monkeypatch.setattr(sr, "for_run", lambda ctx: red)
    matmul = sr.ms_per_run({}, "serving_decode_steps",
                           ("attn.qkv", "attn.out", "mlp", "lm_head"))
    attn = sr.ms_per_run({}, "serving_decode_steps",
                         ("attn.core", "attn.core.chunks", "attn.kv_write"))
    # 7.25 GB of weights at 819 GB/s are 8.9 ms; the cache read is less
    assert 7.0 < matmul < 16.9 and 1.0 < attn < matmul
    assert 95 < sr.coverage_pct({}) <= 100
    # what the compiler hoisted out of the one-trip scan (the Q/K/V
    # weights' re-layout copies) is named after the loop that consumes it
    module = "jit__serving_decode_steps_impl"
    hoisted = red["inherited_s"][module]["decode.steps"] \
        / red["module_runs"][module]
    assert 2e-3 < hoisted < 3e-3


def test_recorded_host_spans_nest(recorded):
    red = sr.reduce(recorded, NAMES)
    h = red["host_s"]
    assert h["serving.drain.wait"] <= h["serving.drain"] <= h["serving.step"]
    assert h["serving.prefill_chunk"] <= h["serving.spend_prefill"]
    assert h["serving.step"] <= red["window_s"]
    assert red["host_own_s"] == pytest.approx(
        h["serving.step"] + h["serving.submit"] - h["serving.drain.wait"],
        rel=1e-6)


class _Req:
    def __init__(self, marks, t_first, status="done"):
        self.status, self.t_first, self._marks = status, t_first, marks

    def timeline(self):
        return self._marks


def test_ttft_legs_add_up_and_need_the_final_mark():
    pf = lambda t, **kw: dict({"t": t, "phase": "prefilling"}, **kw)
    full = _Req([{"t": 9.5, "phase": "queued"}, pf(10.0, slot=1),
                 pf(10.1, chunk=0, final=False), pf(10.3, chunk=1, final=True),
                 {"t": 10.45, "phase": "decoding"}], t_first=10.44)
    old = _Req([pf(20.0), pf(20.1, chunk=0), {"t": 20.3, "phase": "decoding"}],
               t_first=20.3)
    lost = _Req([pf(30.0), pf(30.1, chunk=0, final=True)], None, status=None)
    rec = {"requests": [full, old, lost], "dues": [9.0, 19.0, 29.0]}
    (leg,) = sr.ttft_legs(rec)
    assert leg["queue"] == pytest.approx(1.0)
    assert leg["prefill"] == pytest.approx(0.3)
    assert leg["lag"] == pytest.approx(0.14)
    assert leg["queue"] + leg["prefill"] + leg["lag"] \
        == pytest.approx(leg["ttft"])
    assert sr.leg_p95_ms({"record": rec}, "lag") == pytest.approx(140.0)
    # a program whose marks do not say which chunk was the last
    assert sr.leg_p95_ms({"record": {"requests": [old], "dues": [19.0]}},
                         "prefill") is None


def test_names_are_the_programs_vocabulary():
    from paddle_tpu.observability.trace import LOOPS, SCOPES

    assert set(sr.NAMES) == set(SCOPES + LOOPS)


def test_new_readers_in_a_rehearsal(toy_root, capsys):
    """A traced toy run on the CPU with the new metrics declared: the
    host-side ones read (the spans and the timeline's ``final`` mark are
    there), the device ones find no device plane and are left out."""
    from benchmark import run

    bench = json.load(open(os.path.join(toy_root, "BENCHMARK.json")))
    real = json.load(open(os.path.join(sr.harness.ROOT, "BENCHMARK.json")))
    new = ("decode_attn_ms", "decode_matmul_ms", "prefill_attn_ms",
           "scope_coverage_pct.serve", "sched_host_busy_pct",
           "ttft_prefill_p95_ms", "first_token_lag_p95_ms")
    for e in real["per_layer"]:
        if e["name"] in new:
            bench["per_layer"].append(dict(e, workloads=["toy_chat"]))
    assert len(bench["per_layer"]) >= len(new)
    with open(os.path.join(toy_root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    sr._cache.clear()
    run.main(["--workload", "toy_chat", "--seed", "3000000019", "--seconds",
              "2", "--trace", "1"], require_chip=False, root=toy_root)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = set(line["metrics"]) & set(new)
    assert got == {"sched_host_busy_pct", "ttft_prefill_p95_ms",
                   "first_token_lag_p95_ms"}
    assert 0 < line["metrics"]["sched_host_busy_pct"]["value"] <= 100
    assert line["metrics"]["first_token_lag_p95_ms"]["value"] > 0
