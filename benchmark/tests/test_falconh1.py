"""The Falcon-H1 cell at toy size on the CPU: the serve driver end to end
through the architecture's file, found by name; ``lib/falcon_h1_flops.py``
against hand counts at the published sizes; ``correct`` false under the fp8
control and under a skipped state reset; the new readers over a recorded
scoped trace shape."""
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import falcon_h1_flops as F
from benchmark.lib import falcon_h1_reduce as R
from benchmark.lib import harness

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = ["toy_falconh1_chat"]
NEW = ("falconh1_serve_mfu", "ssm_decode_ms", "ssm_prefill_ms",
       "ssm_state_roofline", "ssm_scan_roofline", "ssm_scope_coverage_pct")


def toy_benchmark():
    e2e = lambda n, u, b: {"name": n, "unit": u, "better": b, "bound": 0.05,
                           "source": "host_clock", "workloads": CELL}
    layer = lambda n, u, src, moves: {
        "name": n, "unit": u, "better": "higher", "source": src,
        "layer": "model step", "moves": moves, "workloads": CELL}
    return {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 30,
        "configs": [{"name": "toy_falconh1", "source": "toy", "reduced": [],
                     "why": "toy",
                     "file": "benchmark/configs/toy_falconh1.json"}],
        "workloads": [{"name": CELL[0], "config": "toy_falconh1",
                       "traffic": "toy_chat", "chips": 1, "why": "toy"}],
        "end_to_end": [
            e2e("ttft_p95_ms", "ms", "lower"),
            e2e("tpot_p95_ms", "ms", "lower"),
            e2e("serve_tokens_per_s", "tokens/s", "higher"),
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [layer("decode_batch_mean", "slots", "program_counter",
                            "serve_tokens_per_s")]
        + [layer(n, "%", "device_trace", "tpot_p95_ms") for n in NEW],
    }


@pytest.fixture
def falcon_root(tmp_path, monkeypatch):
    """A checkout-shaped directory with the toy Falcon-H1 cell (the real
    drivers, generators, models and readers linked)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    root = tmp_path / "root"
    bench = root / "benchmark"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    for kind in ("drivers", "generators", "models", "layer_metrics"):
        os.symlink(os.path.join(BENCH, kind), bench / kind)
    shutil.copy(os.path.join(HERE, "data", "toy_falconh1.json"),
                bench / "configs")
    shutil.copy(os.path.join(HERE, "data", "toy_chat.json"), bench / "traffic")
    (root / "BENCHMARK.json").write_text(json.dumps(toy_benchmark()))
    return str(root)


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_driver_runs_the_cell(falcon_root, capsys, trace):
    run.main(["--workload", CELL[0], "--seed", "3000000019", "--seconds",
              "2", "--trace", str(trace)], require_chip=False,
             root=falcon_root)
    line = last_line(capsys)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["compared"]["logit_gap_max"]["value"] <= 1e-3
    if trace:
        assert "decode_batch_mean" in line["metrics"]
        # nothing ran on a device: the device readers find nothing to read
        # and the line leaves them out
        assert not set(NEW) & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                        "serve_tokens_per_s", "setup_s"}


def drive(root, **driver_kw):
    files = harness.Files(root)
    cell, config, traffic = files.cell(CELL[0])
    device, events = harness.start(1, require_chip=False)
    out = files.named("drivers", "serve").run(
        files=files, cell=cell, config=config, traffic=traffic,
        seed=2147483777, seconds=1.0, trace=False, events=events,
        t_start=time.perf_counter(), **driver_kw)
    out["compared"].print()
    return harness.result_line(files, CELL[0], False, out, device)


def test_skipped_state_reset_comes_out_not_correct(falcon_root, monkeypatch):
    """The fault: the prefill-chunk program no longer takes zeros in the
    state's place at a request's first chunk, so a slot's next tenant
    starts from its predecessor's state (the warm-up's requests have used
    every slot before the window opens)."""
    import dataclasses

    import jax

    from paddle_tpu.models import falcon_h1_decode as fd

    def plant(engine):
        monkeypatch.setattr(fd, "_RESET_AT_ADMISSION", False)

        # a new function object (jit's trace cache is keyed by the
        # function), traced while the seam is off
        def impl(params, cfg, tokens, offset, prompt_len, caches, slot,
                 chunk_size):
            return fd._serving_prefill_chunk_impl(
                params, cfg, tokens, offset, prompt_len, caches, slot,
                chunk_size=chunk_size)

        faulty = jax.jit(impl, static_argnames=("cfg", "chunk_size"))
        engine._fam = dataclasses.replace(
            engine._fam,
            prefill_chunk=lambda params, cfg, tokens, offset, prompt_len,
            caches, slot, chunk_size=None, **_: faulty(
                params, cfg, tokens, offset, prompt_len, caches, slot,
                chunk_size=chunk_size))

    line = drive(falcon_root, before_window=plant)
    assert line["correct"] is False
    assert line["compared"]["logit_gap_max"]["ok"] is False
    assert line["compared"]["requests_failed"]["ok"] is True


def test_control_fp8_reads_above_the_limit(falcon_root):
    files = harness.Files(falcon_root)
    _, config, _ = files.cell(CELL[0])
    serve = files.named("drivers", "serve")
    arch = files.named("models", config["model"])
    rng = np.random.default_rng(5)
    served = [(rng.integers(1, 256, 8).astype(np.int32),
               rng.integers(1, 256, 110)) for _ in range(4)]
    _, ctrl = serve.reference_gaps(arch, config, (128, 110), 9, served,
                                   quants=(None, "fp8"))
    assert ctrl.size == 440
    assert ctrl.max() > 3 * config["check"]["logit_gap_max"]


# ------------------------------------------------------------ hand counts
M = {"hidden_size": 5120, "intermediate_size": 21504, "num_hidden_layers": 6,
     "num_attention_heads": 20, "num_key_value_heads": 4, "head_dim": 128,
     "vocab_size": 261120, "mamba_d_ssm": 4096, "mamba_n_heads": 32,
     "mamba_d_head": 128, "mamba_n_groups": 2, "mamba_d_state": 256,
     "mamba_d_conv": 4, "mamba_chunk_size": 128}


def test_parameters_at_the_published_sizes():
    # q 5120x2560, k and v 5120x512 each, o 2560x5120
    assert F.attention_params(M) == 31_457_280
    # in_proj 5120 x (4096 + 4096 + 512 + 512 + 32), out_proj 4096 x 5120
    assert F.in_proj_width(M) == 9248
    assert F.mixer_params(M) == 5120 * 9248 + 4096 * 5120 == 68_321_280
    assert F.mlp_params(M) == 3 * 5120 * 21504 == 330_301_440
    assert F.layer_matmul_params(M) == 430_080_000
    assert F.lm_head_params(M) == 1_336_934_400
    assert F.matmul_params(M) == 6 * 430_080_000 + 1_336_934_400


def test_state_bytes():
    # 32 heads x 128 x 256 float32 = 4.19 MB, 3 x 5120 bf16 of tail, 6 layers
    assert F.state_elements(M) == 1_048_576
    assert F.state_bytes_per_slot(M) == 6 * (4_194_304 + 3 * 5120 * 2)
    assert F.state_update_bytes(M, 10) == 20 * F.state_bytes_per_slot(M)
    # a decode step at 64 live slots of 400 rows: weights, K/V, state
    kv = 2 * 4 * 128 * 2 * 6
    assert F.decode_step_bytes(M, 64 * 400, 64) == (
        2 * F.matmul_params(M) + kv * (64 * 400 + 64)
        + 128 * F.state_bytes_per_slot(M))


def test_recurrence_operations():
    assert F.conv_flops_per_token(M) == 2 * 4 * 5120 * 6
    assert F.state_update_flops_per_token(M) == 5 * 1_048_576 * 6
    assert F.decode_token_flops(M, 0) == (
        2 * F.matmul_params(M) + 2 * 4 * 5120 * 6 + 5 * 1_048_576 * 6)
    # 256 tokens = 2 SSD chunks of 128: inside a chunk the causal halves of
    # C B^T (128 x 512 a token) and of its product with x (128 x 4096);
    # 2 x 2 x 4096 x 256 a token across chunks; 2 boundaries
    one = 256 * 128 * (512 + 4096) + 256 * 4 * 4096 * 256 + 2 * 2 * 4096 * 256
    assert F.scan_flops(M, 256) == 6 * one
    assert F.scan_bytes(M, 256, 1) == 6 * (
        2 * 4_194_304 + 256 * (5120 * 2 + 32 * 4 + 4096 * 4))
    assert F.prefill_flops(M, 256, with_head=True) == (
        2 * 6 * 430_080_000 * 256 + 4 * 20 * 128 * 6 * (256 * 257 // 2)
        + F.conv_flops_per_token(M) * 256 + F.scan_flops(M, 256)
        + 2 * 1_336_934_400)


# ----------------------------------------------------------------- readers
def scoped_trace():
    """The plain form ``span_reduce`` reads: one device, a decode and a
    prefill-chunk module run, operations under attention, state-space and
    no names, inside two ``bench.`` marks."""
    us = 1000
    dec = "jit__serving_decode_steps_impl(1)"
    pre = "jit__serving_prefill_chunk_impl(2)"
    ops = [
        ["%fusion.1", 0, 40 * us, "jit(f)/decode.steps/while/body/mlp/dot"],
        ["%fusion.2", 40 * us, 30 * us,
         "jit(f)/decode.steps/while/body/ssm.state_update/mul"],
        ["%fusion.3", 70 * us, 10 * us,
         "jit(f)/decode.steps/while/body/ssm.in_proj/dot"],
        ["%fusion.4", 80 * us, 20 * us,
         "jit(f)/decode.steps/while/body/attn.core/dot"],
        ["%fusion.5", 100 * us, 50 * us, "jit(g)/ssm.scan/dot"],
        ["%fusion.6", 150 * us, 25 * us, "jit(g)/ssm.conv/add"],
        ["%fusion.7", 175 * us, 20 * us, "jit(g)/mlp/dot"],
        ["%copy.8", 195 * us, 5 * us, ""],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                [dec, 0, 100 * us, ""], [pre, 100 * us, 100 * us, ""]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.step", 0, 200 * us, {}]]}]}]}


class Req:
    def __init__(self, p, n, t_first, t_done, marks):
        self.prompt_ids, self.output_ids = [0] * p, [0] * n
        self.t_first, self.t_done, self._marks = t_first, t_done, marks

    def timeline(self):
        return [{"phase": "prefilling", "t": t} for t in self._marks]


def test_readers_read_the_state_space_scopes(monkeypatch, tmp_path):
    from benchmark.lib import span_reduce

    monkeypatch.setattr(R, "for_run", lambda ctx: span_reduce.reduce(
        scoped_trace(), R.NAMES, 1))
    files = harness.Files()
    # 10 tokens decoded inside the window, one 100-token prompt prefilled
    reqs = [Req(50, 11, 0.0, 1.0, [-1.0]), Req(100, 1, None, None, [0.5])]
    ctx = {"model": M, "chips": 1, "device_kind": "TPU v5 lite",
           "record": {"requests": reqs, "traced": (0.0, 1.05)},
           "trace": {"window_s": 200e-6}}
    read = lambda name: files.named("layer_metrics", name).read(ctx)
    assert read("ssm_decode_ms") == pytest.approx(0.040)
    assert read("ssm_prefill_ms") == pytest.approx(0.075)
    assert read("ssm_scope_coverage_pct") == pytest.approx(97.5)
    assert R.work(ctx)["decode_tokens"] == 10
    assert R.work(ctx)["prefill_tokens"] == 100
    assert read("ssm_state_roofline") == pytest.approx(
        100 * F.state_update_bytes(M, 10) / 819e9 / 30e-6)
    assert read("ssm_scan_roofline") == pytest.approx(
        100 * F.scan_bytes(M, 100, 1) / 819e9 / 50e-6)
    flops = (sum(F.decode_token_flops(M, 50 + j) for j in range(1, 11))
             + F.prefill_flops(M, 100, with_head=True))
    assert read("falconh1_serve_mfu") == pytest.approx(
        100 * flops / (200e-6 * 197e12))
    # the accepted list alone leaves the state-space work without a name
    old = span_reduce.reduce(scoped_trace(), span_reduce.NAMES, 1)
    assert "ssm.scan" not in old["self_s"]["jit__serving_prefill_chunk_impl"]


def test_readers_return_nothing_for_a_program_without_the_scopes(monkeypatch):
    from benchmark.lib import span_reduce

    plain = scoped_trace()
    for ev in plain["planes"][0]["lines"][1]["events"]:
        ev[3] = ev[3].replace("ssm.", "xyz.")
    monkeypatch.setattr(R, "for_run", lambda ctx: span_reduce.reduce(
        plain, R.NAMES, 1))
    files = harness.Files()
    ctx = {"model": M, "chips": 1, "device_kind": "TPU v5 lite",
           "record": {"requests": [], "traced": (0.0, 1.05)},
           "trace": {"window_s": 200e-6}}
    for name in NEW:
        assert files.named("layer_metrics", name).read(ctx) is None


def test_names_are_the_programs_state_scopes():
    from paddle_tpu.observability.trace import LOOPS, SCOPES, STATE_SCOPES

    assert R.SSM_NAMES == STATE_SCOPES
    assert set(R.NAMES) == set(SCOPES + LOOPS + STATE_SCOPES)
