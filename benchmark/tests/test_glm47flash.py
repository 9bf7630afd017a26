"""The GLM-4.7-Flash cell at toy size on the CPU: the ``serve_routed``
driver end to end through the architecture's file, found by name;
``lib/glm4_moe_lite_flops.py`` against hand counts at the published sizes;
``correct`` false under the fp8 control and under each planted fault (a
token altered where it is produced, one expert's weights swapped for
another's, the gates left unnormalised, the selection made without the
bias); the new readers over a scoped trace shape."""
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import glm4_moe_lite_flops as F
from benchmark.lib import glm4_moe_lite_reduce as R
from benchmark.lib import harness

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = ["toy_glm47flash_code"]
NEW = ("glm47flash_serve_mfu", "moe_decode_ms", "moe_prefill_ms",
       "moe_experts_decode_roofline", "moe_experts_prefill_roofline",
       "mla_latent_roofline", "moe_expert_load_max_over_mean",
       "moe_scope_coverage_pct")


def toy_benchmark():
    e2e = lambda n, u, b: {"name": n, "unit": u, "better": b, "bound": 0.05,
                           "source": "host_clock", "workloads": CELL}
    layer = lambda n, u, src, moves: {
        "name": n, "unit": u, "better": "higher", "source": src,
        "layer": "model step", "moves": moves, "workloads": CELL}
    return {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 30,
        "configs": [{"name": "toy_glm47flash", "source": "toy",
                     "reduced": [], "why": "toy",
                     "file": "benchmark/configs/toy_glm47flash.json"}],
        "workloads": [{"name": CELL[0], "config": "toy_glm47flash",
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
def glm_root(tmp_path, monkeypatch):
    """A checkout-shaped directory with the toy GLM-4.7-Flash cell (the real
    drivers, generators, models and readers linked)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    root = tmp_path / "root"
    bench = root / "benchmark"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    for kind in ("drivers", "generators", "models", "layer_metrics"):
        os.symlink(os.path.join(BENCH, kind), bench / kind)
    shutil.copy(os.path.join(HERE, "data", "toy_glm47flash.json"),
                bench / "configs")
    shutil.copy(os.path.join(HERE, "data", "toy_chat.json"), bench / "traffic")
    (root / "BENCHMARK.json").write_text(json.dumps(toy_benchmark()))
    return str(root)


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_routed_driver_runs_the_cell(glm_root, capsys, trace):
    run.main(["--workload", CELL[0], "--seed", "3000000019", "--seconds",
              "2", "--trace", str(trace)], require_chip=False,
             root=glm_root)
    line = last_line(capsys)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    c = line["compared"]
    assert c["logit_gap_max"]["value"] <= 1e-3
    assert c["routes_refused"]["value"] == 0
    assert c["routes_recorded"]["value"] >= c["served_tokens_compared"]["value"]
    if trace:
        assert "decode_batch_mean" in line["metrics"]
        # the counter's reader needs no device; the device readers find
        # nothing to read and the line leaves them out
        assert line["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1
        assert set(NEW) & set(line["metrics"]) == {
            "moe_expert_load_max_over_mean"}
    else:
        assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                        "serve_tokens_per_s", "setup_s"}


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


def replant(engine, edit_params=None, patch=None):
    """Swap the engine's programs for freshly traced ones (jit's trace
    cache is keyed by the function object) over edited weights or a patched
    routing rule."""
    import dataclasses

    import jax

    from paddle_tpu.models import glm4_moe_lite_decode as gd

    if edit_params is not None:
        engine._params = edit_params(engine._params)

    def decode(params, cfg, cur, caches, dev_lengths, n_steps, chunk_size):
        return gd._serving_decode_steps_impl(
            params, cfg, cur, caches, dev_lengths, n_steps=n_steps,
            chunk_size=chunk_size)

    def prefill(params, cfg, tokens, offset, prompt_len, caches, slot,
                chunk_size):
        return gd._serving_prefill_chunk_impl(
            params, cfg, tokens, offset, prompt_len, caches, slot,
            chunk_size=chunk_size)

    jd = jax.jit(decode, static_argnames=("cfg", "n_steps", "chunk_size"))
    jp = jax.jit(prefill, static_argnames=("cfg", "chunk_size"))
    if patch is not None:
        patch()
    engine._fam = dataclasses.replace(
        engine._fam,
        decode_steps=lambda params, cfg, cur, caches, dev_lengths, n_steps=1,
        chunk_size=None, **_: jd(params, cfg, cur, caches, dev_lengths,
                                 n_steps=n_steps, chunk_size=chunk_size),
        prefill_chunk=lambda params, cfg, tokens, offset, prompt_len, caches,
        slot, chunk_size=None, **_: jp(params, cfg, tokens, offset,
                                       prompt_len, caches, slot,
                                       chunk_size=chunk_size))


def test_swapped_expert_comes_out_not_correct(glm_root):
    """The fault: expert 0's weights stand in expert 1's place in every
    expert layer — the first expert layer's routes are the reference's
    own, the logits are not (further down the hidden states, and with them
    some routes, are already wrong)."""
    def swap(params):
        layers = []
        for lp in params["layers"]:
            lp = dict(lp)
            for k in ("e_gate", "e_up", "e_down"):
                if k in lp:
                    lp[k] = lp[k].at[1].set(lp[k][0])
            layers.append(lp)
        return dict(params, layers=layers)

    line = drive(glm_root, before_window=lambda e: replant(e, swap))
    assert line["correct"] is False
    assert line["compared"]["logit_gap_max"]["ok"] is False
    assert line["compared"]["requests_failed"]["ok"] is True


def test_unnormalised_gates_come_out_not_correct(glm_root, monkeypatch):
    """The fault: the chosen scores weigh the experts as they are, without
    the division by their sum."""
    from paddle_tpu.ops import moe

    real = moe.route

    def raw(x, w_r, bias, k, scale):
        experts, gates = real(x, w_r, bias, k, scale)
        import jax
        import jax.numpy as jnp
        s = jax.nn.sigmoid(jnp.matmul(
            x, w_r, preferred_element_type=jnp.float32))
        return experts, scale * jnp.take_along_axis(s, experts, -1)

    from paddle_tpu.models import glm4_moe_lite
    line = drive(glm_root, before_window=lambda e: replant(
        e, patch=lambda: monkeypatch.setattr(glm4_moe_lite, "route", raw)))
    assert line["correct"] is False
    assert line["compared"]["logit_gap_max"]["ok"] is False


def test_selection_without_the_bias_comes_out_not_correct(glm_root,
                                                          monkeypatch):
    """The fault: the top experts are taken of the unbiased scores.  Every
    recorded choice that the bias would have changed lies outside the
    margin by the reference's own scores: refused, not followed."""
    from paddle_tpu.models import glm4_moe_lite
    from paddle_tpu.ops import moe

    real = moe.route
    unbiased = lambda x, w_r, bias, k, scale: real(x, w_r, bias * 0, k,
                                                   scale)
    line = drive(glm_root, before_window=lambda e: replant(
        e, patch=lambda: monkeypatch.setattr(glm4_moe_lite, "route",
                                             unbiased)))
    assert line["correct"] is False
    assert line["compared"]["routes_refused"]["ok"] is False


def test_altered_token_comes_out_not_correct(glm_root):
    """The fault: a token altered where it is produced — the engine's
    sampler hands back the runner-up."""
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
        line = drive(glm_root, before_window=lambda e: replant(
            e, patch=patch))
    finally:
        gd._greedy_pick = real
    assert line["correct"] is False
    assert line["compared"]["logit_gap_max"]["ok"] is False


def test_control_fp8_reads_above_the_limit(glm_root):
    """The fp8 control in the program's place, through the driver's own
    comparison: the same window's sample, the control's chosen sets
    followed within the margin like recorded ones, its first-ranked tokens
    for the served ones."""
    line = drive(glm_root, control="fp8")
    assert line["correct"] is False
    c = line["compared"]
    assert c["requests_failed"]["ok"] and c["routes_recorded"]["ok"]
    assert c["logit_gap_max"]["value"] > 3 * c["logit_gap_max"]["limit"]
    assert c["routes_refused"]["ok"] is False


def test_a_pass_hands_back_the_sets_it_used(glm_root):
    """``chosen=``: the reference's own sets, followed, are followed
    nowhere and refused nowhere (they ARE its own); the control's differ
    from them, which is what its reading rests on."""
    files = harness.Files(glm_root)
    _, config, _ = files.cell(CELL[0])
    arch = files.named("models", config["model"])
    rng = np.random.default_rng(7)
    tokens = rng.integers(1, 256, (2, 40)).astype(np.int32)
    rows = np.arange(40, dtype=np.int32)[None].repeat(2, 0)
    sets = []
    own, _ = arch.serve_logits(config, 6, tokens, rows, quants=(None, "fp8"),
                               chosen=sets)
    assert [s.shape for s in sets] == [(2, 40, 2, 2)] * 2
    st = {}
    again, = arch.serve_logits(config, 6, tokens, rows, routes=sets[0],
                               stats=st)
    assert (st["recorded"], st["differ"], st["refused"]) == (160, 0, 0)
    np.testing.assert_array_equal(again, own)
    same = (np.sort(sets[0], -1) == np.sort(sets[1], -1)).all(-1)
    assert 0.3 < same.mean() < 1.0


def test_reference_refuses_an_illegitimate_route(glm_root):
    """A recorded set that the reference's own scores do not support (one
    fixed pair for every position) is refused wherever it lies outside the
    margin, and the reference keeps its own choice there; with a margin
    that admits anything the planted routes ARE followed and the logits
    move."""
    from benchmark.lib import glm4_moe_lite_ref as ref

    files = harness.Files(glm_root)
    _, config, _ = files.cell(CELL[0])
    arch = files.named("models", config["model"])
    rng = np.random.default_rng(2)
    tokens = rng.integers(1, 256, (1, 24)).astype(np.int32)
    rows = np.arange(24, dtype=np.int32)[None]
    own, = arch.serve_logits(config, 4, tokens, rows)
    bad = np.full((1, 24, 2, 2), -1, np.int32)
    bad[0, :, 0] = [6, 7]          # the first expert layer, every position
    st = {}
    arch.serve_logits(config, 4, tokens, rows, routes=bad, stats=st)
    assert st["recorded"] == 24 and st["refused"] > 0
    assert st["refused"] + st["followed"] == st["differ"] <= 24
    followed, = ref.serve_logits(arch.sizes(config), 4, "float32", tokens,
                                 rows, routes=bad, route_margin=10.0)
    assert np.abs(followed - own).max() > 1e-3


# ------------------------------------------------------------ hand counts
M = {"hidden_size": 2048, "intermediate_size": 10240,
     "moe_intermediate_size": 1536, "num_hidden_layers": 7,
     "first_k_dense_replace": 1, "num_attention_heads": 20,
     "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
     "qk_rope_head_dim": 64, "v_head_dim": 256, "n_routed_experts": 64,
     "n_shared_experts": 1, "num_experts_per_tok": 4, "vocab_size": 154880}


def test_parameters_at_the_published_sizes():
    # W_dq 2048x768, W_uq 768x5120, W_dkv 2048x576, W_ukv 512x8960,
    # W_o 5120x2048
    assert F.attention_params(M) == (1_572_864 + 3_932_160 + 1_179_648
                                     + 4_587_520 + 10_485_760) == 21_757_952
    assert F.expert_params(M) == 3 * 2048 * 1536 == 9_437_184
    assert F.router_params(M) == 131_072
    assert F.moe_layer_params(M) == 21_757_952 + 131_072 + 65 * 9_437_184 \
        == 635_305_984
    assert F.dense_layer_params(M) == 21_757_952 + 62_914_560
    assert F.lm_head_params(M) == 317_194_240
    # a token: the dense layer, 6 x (attention + router + 5 experts), head
    assert F.moe_layer_active_params(M) == 69_074_944
    assert F.active_params(M) == 84_672_512 + 6 * 69_074_944 + 317_194_240 \
        == 816_316_416
    # what the chip holds: 9.06 GB of weights with the embedding
    held = (F.dense_layer_params(M) + 6 * F.moe_layer_params(M)
            + 2 * F.lm_head_params(M))
    assert round(2 * held / 1e9, 2) == 9.06


def test_attention_and_expert_work():
    assert F.row_width(M) == 576
    # a (query, row) pair: 20 heads x (576 score + 512 value) x 2, 7 layers
    assert F.attn_flops_per_pair(M) == 2 * 20 * 1088 * 7 == 304_640
    assert F.decode_token_flops(M, 1000) == (2 * 816_316_416
                                             + 304_640 * 1000)
    assert F.prefill_flops(M, 256, with_head=True) == (
        2 * (816_316_416 - 317_194_240) * 256 + 304_640 * (256 * 257 // 2)
        + 2 * 317_194_240)
    assert F.latent_bytes(M, 10) == 10 * 1152
    assert F.experts_flops(M, 100) == 2 * 9_437_184 * 100
    # 50 experts touched, 100 pairs: weights once each, the pairs'
    # activations (2 x 2048 + 3 x 1536 values a pair)
    assert F.experts_bytes(M, 50, 100) == 2 * (50 * 9_437_184 + 100 * 8704)


# ----------------------------------------------------------------- readers
def scoped_trace():
    us = 1000
    dec = "jit__serving_decode_steps_impl(1)"
    pre = "jit__serving_prefill_chunk_impl(2)"
    ops = [
        ["%fusion.1", 0, 40 * us,
         "jit(f)/decode.steps/while/body/moe.experts/pallas_call"],
        ["%fusion.2", 40 * us, 10 * us,
         "jit(f)/decode.steps/while/body/moe.dispatch/sort"],
        ["%fusion.3", 50 * us, 10 * us,
         "jit(f)/decode.steps/while/body/mla.absorb/dot"],
        ["%fusion.4", 60 * us, 20 * us,
         "jit(f)/decode.steps/while/body/attn.core/attn.core.chunks/dot"],
        ["%fusion.9", 80 * us, 20 * us,
         "jit(f)/decode.steps/while/body/lm_head/dot"],
        ["%fusion.5", 100 * us, 50 * us, "jit(g)/moe.experts/pallas_call"],
        ["%fusion.6", 150 * us, 25 * us, "jit(g)/moe.shared/dot"],
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


MOE = {"pairs": {"0": 300.0, "1": 100.0, "5": 200.0},
       "touched": {"decode": 500.0, "prefill": 120.0},
       "dispatches": {"decode": 10.0, "prefill": 2.0}}


def test_readers_read_the_expert_and_latent_scopes(monkeypatch):
    from benchmark.lib import span_reduce

    monkeypatch.setattr(R, "for_run", lambda ctx: span_reduce.reduce(
        scoped_trace(), R.NAMES, 1))
    files = harness.Files()
    # 10 tokens decoded inside the window, one 100-token prompt prefilled
    reqs = [Req(50, 11, 0.0, 1.0, [-1.0]), Req(100, 1, None, None, [0.5])]
    ctx = {"model": M, "chips": 1, "device_kind": "TPU v5 lite",
           "record": {"requests": reqs, "traced": (0.0, 1.05), "moe": MOE},
           "trace": {"window_s": 200e-6}}
    read = lambda name: files.named("layer_metrics", name).read(ctx)
    assert read("moe_decode_ms") == pytest.approx(0.050)
    assert read("moe_prefill_ms") == pytest.approx(0.075)
    assert read("moe_scope_coverage_pct") == pytest.approx(97.5)
    # time that carries the step loop's name and no finer one is not covered
    loose = scoped_trace()
    loose["planes"][0]["lines"][1]["events"][4][3] = \
        "jit(f)/decode.steps/while/body/add"
    monkeypatch.setattr(R, "for_run", lambda ctx: span_reduce.reduce(
        loose, R.NAMES, 1))
    assert read("moe_scope_coverage_pct") == pytest.approx(87.5)
    monkeypatch.setattr(R, "for_run", lambda ctx: span_reduce.reduce(
        scoped_trace(), R.NAMES, 1))
    w = R.work(ctx)
    assert (w["decode_tokens"], w["prefill_tokens"]) == (10, 100)
    assert w["decode_context_rows"] == sum(50 + j for j in range(1, 11))
    # 50 experts touched a decode run (6 layers together), 1 run traced
    assert read("moe_experts_decode_roofline") == pytest.approx(
        100 * F.experts_bytes(M, 50, 10 * 4 * 6) / 819e9 / 40e-6)
    assert read("moe_experts_prefill_roofline") == pytest.approx(
        100 * max(F.experts_bytes(M, 60, 100 * 4 * 6) / 819e9,
                  F.experts_flops(M, 100 * 4 * 6) / 197e12) / 50e-6)
    assert read("mla_latent_roofline") == pytest.approx(
        100 * 7 * F.latent_bytes(M, w["decode_context_rows"] + 10)
        / 819e9 / 20e-6)
    assert read("moe_expert_load_max_over_mean") == pytest.approx(
        300 * 64 / 600)
    flops = (sum(F.decode_token_flops(M, 50 + j) for j in range(1, 11))
             + F.prefill_flops(M, 100, with_head=True))
    assert read("glm47flash_serve_mfu") == pytest.approx(
        100 * flops / (200e-6 * 197e12))
    # the accepted list alone leaves the expert work without a name
    old = span_reduce.reduce(scoped_trace(), span_reduce.NAMES, 1)
    assert "moe.experts" not in old["self_s"][
        "jit__serving_prefill_chunk_impl"]


def test_readers_return_nothing_for_a_program_without_the_scopes(monkeypatch):
    from benchmark.lib import span_reduce

    plain = scoped_trace()
    for ev in plain["planes"][0]["lines"][1]["events"]:
        ev[3] = ev[3].replace("moe.", "xyz.").replace("mla.", "xyz.") \
            .replace("attn.core", "xyz")
    monkeypatch.setattr(R, "for_run", lambda ctx: span_reduce.reduce(
        plain, R.NAMES, 1))
    files = harness.Files()
    ctx = {"model": M, "chips": 1, "device_kind": "TPU v5 lite",
           "record": {"requests": [], "traced": (0.0, 1.05)},
           "trace": {"window_s": 200e-6}}
    for name in NEW:
        assert files.named("layer_metrics", name).read(ctx) is None


def test_names_are_the_programs_expert_scopes():
    from paddle_tpu.observability.trace import (EXPERT_SCOPES, LOOPS, SCOPES)

    assert R.MOE_NAMES + R.MLA_NAMES == EXPERT_SCOPES
    assert set(R.NAMES) == set(SCOPES + LOOPS + EXPERT_SCOPES)
