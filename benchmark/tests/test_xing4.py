"""The Xing4.0-29B-A4B cell at toy size on the CPU: the ``serve_routed``
driver end to end through the architecture's file, found by name;
``lib/xing4_flops.py`` against hand counts at the published sizes; the
accepted ``moe_*`` / ``mla_*`` readers against hand counts at THIS model's
sizes; ``correct`` false under the fp8 control and under each planted fault
(the mixing left out, ``H_post`` without its factor 2, the YaRN scale left
out, one Sinkhorn step in place of 20, and GLM's four: a token altered
where it is produced, one expert's weights swapped for another's, the gates
left unnormalised, the selection made without the bias); the new readers
over a scoped trace shape."""
import json
import os
import shutil
import time

import pytest

from benchmark import run
from benchmark.lib import glm4_moe_lite_flops as GF
from benchmark.lib import glm4_moe_lite_reduce as GR
from benchmark.lib import harness
from benchmark.lib import xing4_flops as F
from benchmark.lib import xing4_reduce as R
from benchmark.tests import test_glm47flash as glm_tests
from benchmark.tests.test_glm47flash import MOE, Req, last_line

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = ["toy_xing4_reason"]
REAL = "xing4_reason_steady"
NEW = ("xing4_serve_mfu", "hc_decode_ms", "hc_prefill_ms",
       "hc_decode_roofline", "hc_prefill_roofline", "hc_scope_coverage_pct")
JOINED = ("moe_decode_ms", "moe_prefill_ms", "moe_experts_decode_roofline",
          "moe_experts_prefill_roofline", "mla_latent_roofline",
          "moe_expert_load_max_over_mean")


def toy_benchmark():
    """The real ``BENCHMARK.json`` cut to the new cell under a toy name:
    every metric the cell reports, with the entries as they are."""
    real = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    mine = lambda e: "workloads" not in e or REAL in e["workloads"]
    rename = lambda e: dict(e, workloads=CELL) if "workloads" in e else e
    return dict(
        real,
        configs=[{"name": "toy_xing4", "source": "toy", "reduced": [],
                  "why": "toy",
                  "file": "benchmark/configs/toy_xing4.json"}],
        workloads=[{"name": CELL[0], "config": "toy_xing4",
                    "traffic": "toy_chat", "chips": 1, "why": "toy"}],
        end_to_end=[rename(e) for e in real["end_to_end"] if mine(e)],
        per_layer=[rename(e) for e in real["per_layer"] if mine(e)])


@pytest.fixture
def xing_root(tmp_path, monkeypatch):
    """A checkout-shaped directory with the toy Xing4 cell (the real
    drivers, generators, models and readers linked)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    root = tmp_path / "root"
    bench = root / "benchmark"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    for kind in ("drivers", "generators", "models", "layer_metrics"):
        os.symlink(os.path.join(BENCH, kind), bench / kind)
    shutil.copy(os.path.join(HERE, "data", "toy_xing4.json"),
                bench / "configs")
    shutil.copy(os.path.join(HERE, "data", "toy_chat.json"), bench / "traffic")
    (root / "BENCHMARK.json").write_text(json.dumps(toy_benchmark()))
    return str(root)


def test_the_real_cell_reports_what_the_issue_names():
    files = harness.Files()
    cell, config, traffic = files.cell(REAL)
    assert (cell["chips"], config["model"], config["kind"]) == (
        1, "xing4", "serve_routed")
    assert {e["name"] for e in files.metrics("end_to_end", REAL)} == {
        "tpot_p95_ms", "serve_tokens_per_s", "setup_s"}
    layer = {e["name"] for e in files.metrics("per_layer", REAL)}
    assert set(NEW) | set(JOINED) <= layer
    assert not layer & {"glm47flash_serve_mfu", "moe_scope_coverage_pct",
                        "decode_mfu", "serve_mfu", "decode_hbm_roofline"}
    for name in NEW:
        entry = harness.find(files.bench["per_layer"], name, "metric")
        assert entry["workloads"] == [REAL]
    assert (traffic["prompt_len"], traffic["output_len"]) == (
        {"dist": "log_uniform", "min": 128, "max": 1024},
        {"dist": "log_uniform", "min": 256, "max": 1024})
    # the catalog row's widths, the depth alone cut
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 40}
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["vocab_size"]) == (
        8, 2, 64, 131072)
    assert config["engine"] == {"batch_size": 64, "max_len": 2304}


@pytest.mark.parametrize("trace", [0, 1])
def test_routed_driver_runs_the_cell(xing_root, capsys, trace):
    run.main(["--workload", CELL[0], "--seed", "3000000019", "--seconds",
              "2", "--trace", str(trace)], require_chip=False,
             root=xing_root)
    line = last_line(capsys)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    c = line["compared"]
    assert c["logit_gap_max"]["value"] <= 1e-3
    assert c["routes_refused"]["value"] == 0
    assert c["routes_recorded"]["value"] >= c["served_tokens_compared"]["value"]
    if trace:
        # the counters' readers need no device; the device readers find
        # nothing to read and the line leaves them out
        assert line["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1
        assert "decode_batch_mean" in line["metrics"]
        assert not set(NEW) & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"tpot_p95_ms", "serve_tokens_per_s",
                                        "setup_s"}


def drive(root, **driver_kw):
    files = harness.Files(root)
    cell, config, traffic = files.cell(CELL[0])
    device, events = harness.start(1, require_chip=False)
    out = files.named("drivers", "serve_routed").run(
        files=files, cell=cell, config=config, traffic=traffic,
        seed=2147483777, seconds=1.0, trace=False, events=events,
        t_start=time.perf_counter(), **driver_kw)
    out["compared"].print()
    return harness.result_line(files, CELL[0], False, out, device)


def replant(engine, edit_params=None, patch=None, edit_cfg=None):
    """``test_glm47flash.replant`` (fresh jits of the two programs over
    edited weights or a patched rule), and edited statics."""
    if edit_cfg is not None:
        engine._cfg = engine._cfg._replace(**edit_cfg)
    glm_tests.replant(engine, edit_params, patch)


def not_correct(line):
    """The run came out not correct by a limit of the comparison (and not
    by a request that failed or a compile in the window)."""
    c = line["compared"]
    assert line["correct"] is False, c
    assert c["requests_failed"]["ok"] and c["routes_recorded"]["ok"]
    assert not (c["logit_gap_max"]["ok"] and c["routes_refused"]["ok"]
                and c["routes_followed_share"]["ok"])
    return c


# ---------------------------------------- the residual path's planted faults
@pytest.mark.parametrize("what,edit_cfg", [
    ("one Sinkhorn step in place of 20", dict(hc_iters=1)),
    ("the YaRN factor left out of the softmax scale", dict(scale_mult=1.0)),
])
def test_edited_statics_come_out_not_correct(xing_root, what, edit_cfg):
    not_correct(drive(xing_root, before_window=lambda e: replant(
        e, edit_cfg=edit_cfg)))


@pytest.mark.parametrize("fault", ["mixing_left_out", "post_without_2"])
def test_planted_hyper_connection_fault_comes_out_not_correct(
        xing_root, monkeypatch, fault):
    """``H_res`` = I (each stream keeps itself: the hyper-connection
    without its mixing), and ``H_post`` = sigma in place of 2 sigma."""
    import jax.numpy as jnp

    from paddle_tpu.ops import hyper_connection

    real = hyper_connection.coefficients

    def planted(X, *a, **kw):
        pre, post, res = real(X, *a, **kw)
        if fault == "post_without_2":
            return pre, tuple(p / 2 for p in post), res
        one, zero = jnp.ones_like(pre[0]), jnp.zeros_like(pre[0])
        n = len(pre)
        return pre, post, tuple(tuple(one if i == j else zero
                                      for j in range(n)) for i in range(n))

    c = not_correct(drive(xing_root, before_window=lambda e: replant(
        e, patch=lambda: monkeypatch.setattr(
            hyper_connection, "coefficients", planted))))
    assert c["logit_gap_max"]["ok"] is False


# ------------------------------------------------ GLM's four on this model
def test_swapped_expert_comes_out_not_correct(xing_root):
    def swap(params):
        layers = []
        for lp in params["layers"]:
            lp = dict(lp)
            for k in ("e_gate", "e_up", "e_down"):
                if k in lp:
                    lp[k] = lp[k].at[1].set(lp[k][0])
            layers.append(lp)
        return dict(params, layers=layers)

    not_correct(drive(xing_root, before_window=lambda e: replant(e, swap)))


def test_unnormalised_gates_come_out_not_correct(xing_root, monkeypatch):
    from paddle_tpu.models import glm4_moe_lite
    from paddle_tpu.ops import moe

    real = moe.route

    def raw(x, w_r, bias, k, scale):
        import jax
        import jax.numpy as jnp
        experts, _ = real(x, w_r, bias, k, scale)
        s = jax.nn.sigmoid(jnp.matmul(
            x, w_r, preferred_element_type=jnp.float32))
        return experts, scale * jnp.take_along_axis(s, experts, -1)

    c = not_correct(drive(xing_root, before_window=lambda e: replant(
        e, patch=lambda: monkeypatch.setattr(glm4_moe_lite, "route", raw))))
    assert c["logit_gap_max"]["ok"] is False


def test_selection_without_the_bias_comes_out_not_correct(xing_root,
                                                          monkeypatch):
    from paddle_tpu.models import glm4_moe_lite
    from paddle_tpu.ops import moe

    real = moe.route
    unbiased = lambda x, w_r, bias, k, scale: real(x, w_r, bias * 0, k,
                                                   scale)
    c = not_correct(drive(xing_root, before_window=lambda e: replant(
        e, patch=lambda: monkeypatch.setattr(glm4_moe_lite, "route",
                                             unbiased))))
    assert c["routes_refused"]["ok"] is False


def test_altered_token_comes_out_not_correct(xing_root):
    import jax.numpy as jnp

    from paddle_tpu.models import glm4_moe_lite_decode as gd

    real = gd._greedy_pick

    def second(logits):
        tok, ok = real(logits)
        masked = jnp.where(jnp.arange(logits.shape[-1])[None] == tok[:, None],
                           -jnp.inf, logits)
        return jnp.argmax(masked, -1).astype(jnp.int32), ok

    def patch():
        gd._greedy_pick = second

    try:
        line = drive(xing_root, before_window=lambda e: replant(
            e, patch=patch))
    finally:
        gd._greedy_pick = real
    assert not_correct(line)["logit_gap_max"]["ok"] is False


def test_control_fp8_reads_above_the_limit(xing_root):
    line = drive(xing_root, control="fp8")
    c = not_correct(line)
    assert c["logit_gap_max"]["value"] > 3 * c["logit_gap_max"]["limit"]
    assert c["routes_refused"]["ok"] is False


# ------------------------------------------------------------ hand counts
M = {"hidden_size": 3584, "intermediate_size": 9216,
     "moe_intermediate_size": 1024, "num_hidden_layers": 8,
     "first_k_dense_replace": 2, "num_attention_heads": 32,
     "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
     "qk_rope_head_dim": 64, "v_head_dim": 128, "n_routed_experts": 64,
     "n_shared_experts": 1, "num_experts_per_tok": 4, "vocab_size": 131072,
     "hc_mult": 4}


def test_parameters_at_the_published_sizes():
    # W_dq 3584x768, W_uq 768x6144, W_dkv 3584x576, W_ukv 512x32x256,
    # W_o 4096x3584: the issue's 2.75 + 4.72 + 2.06 + 4.19 + 14.68 M
    assert F.attention_params(M) == (2_752_512 + 4_718_592 + 2_064_384
                                     + 4_194_304 + 14_680_064) == 28_409_856
    assert F.expert_params(M) == 3 * 3584 * 1024 == 11_010_048
    # a sub-layer's phi [4 x 3584, 24]; two a layer: 0.69 M
    assert F.hc_params(M) == 14_336 * 24 == 344_064
    assert F.moe_layer_params(M) == (28_409_856 + 3584 * 64
                                     + 65 * 11_010_048 + 688_128) \
        == 744_980_480                                          # 745.0 M
    assert F.dense_layer_params(M) == (28_409_856 + 3 * 3584 * 9216
                                       + 688_128) == 128_188_416  # 128.2 M
    assert F.lm_head_params(M) == 469_762_048                  # 469.8 M
    # a token in an expert layer: attention + router + 5 experts + phi
    assert F.moe_layer_active_params(M) == 84_377_600           # 84.4 M
    assert F.active_params(M) == (2 * 128_188_416 + 6 * 84_377_600
                                  + 469_762_048) == 1_232_404_480
    # what the chip holds: 11.33 GB at 2 bytes a parameter (the float32
    # phi counted at 2 as the issue does; 22 MB more as stored)
    held = (2 * F.dense_layer_params(M) + 6 * F.moe_layer_params(M)
            + 2 * F.lm_head_params(M))
    assert round(2 * held / 1e9, 2) == 11.33


def test_hyper_connection_work():
    assert F.hc_columns(M) == 24 and F.hc_sublayers(M) == 16
    # 2 x nC x (n^2 + 2n) a sub-layer
    assert F.hc_flops_per_token(M) == 2 * 14_336 * 24 * 16
    assert F.decode_token_flops(M, 1000) == (
        GF.decode_token_flops(M, 1000) + 2 * 14_336 * 24 * 16)
    assert F.prefill_flops(M, 256, with_head=True) == (
        GF.prefill_flops(M, 256, with_head=True) + 256 * 2 * 14_336 * 24 * 16)
    # 13 C x 2 B a row and sub-layer, phi (float32) once a run
    assert F.hc_bytes(M, 50, 3) == 16 * (50 * 13 * 3584 * 2
                                         + 3 * 344_064 * 4)


# ----------------------------------------------------------------- readers
def scoped_trace():
    us = 1000
    dec = "jit__serving_decode_steps_impl(1)"
    pre = "jit__serving_prefill_chunk_impl(2)"
    body = "jit(f)/decode.steps/while/body/"
    ops = [
        ["%fusion.1", 0, 40 * us, body + "moe.experts/pallas_call"],
        ["%fusion.2", 40 * us, 10 * us, body + "hc.coeff/jit(_c)/dot"],
        ["%fusion.3", 50 * us, 6 * us, body + "hc.read/mul"],
        ["%fusion.4", 56 * us, 4 * us, body + "hc.write/concatenate"],
        ["%fusion.5", 60 * us, 20 * us,
         body + "attn.core/attn.core.chunks/dot"],
        ["%fusion.6", 80 * us, 20 * us, body + "lm_head/dot"],
        ["%fusion.7", 100 * us, 50 * us, "jit(g)/moe.experts/pallas_call"],
        ["%fusion.8", 150 * us, 25 * us, "jit(g)/hc.write/concatenate"],
        ["%fusion.9", 175 * us, 20 * us, "jit(g)/mlp/dot"],
        ["%copy.10", 195 * us, 5 * us, ""],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                [dec, 0, 100 * us, ""], [pre, 100 * us, 100 * us, ""]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.step", 0, 200 * us, {}]]}]}]}


def ctx_of(reqs):
    return {"model": M, "chips": 1, "device_kind": "TPU v5 lite",
            "record": {"requests": reqs, "traced": (0.0, 1.05), "moe": MOE},
            "trace": {"window_s": 200e-6}}


def test_readers_read_the_hyper_connection_scopes(monkeypatch):
    from benchmark.lib import span_reduce

    reduce_with = lambda names: lambda ctx: span_reduce.reduce(
        scoped_trace(), names, 1)
    monkeypatch.setattr(R, "for_run", reduce_with(R.NAMES))
    monkeypatch.setattr(GR, "for_run", reduce_with(GR.NAMES))
    files = harness.Files()
    # 10 tokens decoded inside the window, one 100-token prompt prefilled
    ctx = ctx_of([Req(50, 11, 0.0, 1.0, [-1.0]),
                  Req(100, 1, None, None, [0.5])])
    read = lambda name: files.named("layer_metrics", name).read(ctx)
    assert read("hc_decode_ms") == pytest.approx(0.020)
    assert read("hc_prefill_ms") == pytest.approx(0.025)
    assert read("hc_scope_coverage_pct") == pytest.approx(97.5)
    assert read("hc_decode_roofline") == pytest.approx(
        100 * F.hc_bytes(M, 10, 1) / 819e9 / 20e-6)
    assert read("hc_prefill_roofline") == pytest.approx(
        100 * F.hc_bytes(M, 100, 1) / 819e9 / 25e-6)
    flops = (sum(F.decode_token_flops(M, 50 + j) for j in range(1, 11))
             + F.prefill_flops(M, 100, with_head=True))
    assert read("xing4_serve_mfu") == pytest.approx(
        100 * flops / (200e-6 * 197e12))
    # the accepted readers at THIS model's sizes, unchanged: their counts
    # are functions of ctx["model"] (8 layers, 6 of them expert layers,
    # experts of 3 x 3584 x 1024, rows of 576 values)
    assert read("moe_decode_ms") == pytest.approx(0.040)
    assert read("moe_prefill_ms") == pytest.approx(0.050)
    w = GR.work(ctx)
    assert (w["decode_tokens"], w["prefill_tokens"]) == (10, 100)
    pair_bytes = 2 * (2 * 3584 + 3 * 1024)
    assert read("moe_experts_decode_roofline") == pytest.approx(
        100 * (50 * 11_010_048 * 2 + 10 * 4 * 6 * pair_bytes)
        / 819e9 / 40e-6)
    assert read("moe_experts_prefill_roofline") == pytest.approx(
        100 * max((60 * 11_010_048 * 2 + 100 * 4 * 6 * pair_bytes) / 819e9,
                  2 * 11_010_048 * 100 * 4 * 6 / 197e12) / 50e-6)
    rows = sum(50 + j for j in range(1, 11)) + 10
    assert read("mla_latent_roofline") == pytest.approx(
        100 * 8 * rows * 576 * 2 / 819e9 / 20e-6)
    assert read("moe_expert_load_max_over_mean") == pytest.approx(
        300 * 64 / 600)
    # with the accepted cell's list the hyper-connection's time carries the
    # step loop's name alone, which is why that cell's coverage and MFU
    # readers are not joined
    glm = span_reduce.reduce(scoped_trace(), GR.NAMES, 1)
    table = glm["self_s"]["jit__serving_decode_steps_impl"]
    assert "hc.coeff" not in table and table["decode.steps"] == \
        pytest.approx(20e-6)


def test_readers_return_nothing_for_a_program_without_the_scopes(monkeypatch):
    from benchmark.lib import span_reduce

    plain = scoped_trace()
    for ev in plain["planes"][0]["lines"][1]["events"]:
        ev[3] = ev[3].replace("hc.", "xyz.")
    monkeypatch.setattr(R, "for_run", lambda ctx: span_reduce.reduce(
        plain, R.NAMES, 1))
    files = harness.Files()
    ctx = ctx_of([Req(50, 11, 0.0, 1.0, [-1.0])])
    for name in NEW:
        if name != "xing4_serve_mfu":           # counts tokens, not scopes
            assert files.named("layer_metrics", name).read(ctx) is None
    empty = dict(ctx_of([]), record={"requests": [], "traced": (0.0, 1.05)})
    for name in NEW:
        assert files.named("layer_metrics", name).read(empty) is None
    # no trace at all (a parent without the cell's files, a CPU run)
    monkeypatch.setattr(R, "for_run", lambda ctx: None)
    for name in NEW:
        if name != "xing4_serve_mfu":
            assert files.named("layer_metrics", name).read(ctx) is None


def test_names_are_the_programs_scopes():
    from paddle_tpu.observability.trace import (EXPERT_SCOPES, LOOPS,
                                                RESIDUAL_SCOPES, SCOPES)

    assert R.HC_NAMES == RESIDUAL_SCOPES
    assert R.NAMES == GR.NAMES + RESIDUAL_SCOPES
    assert set(R.NAMES) == set(SCOPES + LOOPS + EXPERT_SCOPES
                               + RESIDUAL_SCOPES)
