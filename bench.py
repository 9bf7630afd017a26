"""Benchmark entry: one JSON line for the driver.

Primary metric — the flagship Llama-style causal-LM training step (fwd+bwd+
AdamW fused into one XLA program via paddle_tpu.static.functionalize) in bf16
on the available chip: a ~0.95B-parameter model at batch 16 x seq 2048 with
chunked big-vocab cross-entropy (full fp32 logits never materialize), int8/bf16
Adam moments, Pallas flash-attention fwd+bwd, and per-layer recompute on the
first 13 of 16 layers (the last 3 keep activations — HBM freed by the loss
chunking and 8-bit moments buys back recompute FLOPs; config picked by a
round-3 sweep on an older stack whose record was removed at bring-up —
re-run bench_sweep.py on the chip before trusting it).

Also records secondary north-star metrics (BASELINE.md): ResNet-50 training
images/sec, eager-mode dispatch throughput (the dygraph path through the
per-op jit cache), and fleet.collective_perf allreduce bandwidth.

Reports **MFU** (analytic model FLOPs per token x tokens/sec / peak chip
FLOPs).  ``vs_baseline`` is the ratio of achieved MFU against the first MFU
recorded on this hardware (bench_baseline.json).

Every JSON line printed carries the device it ran on (``device``:
platform, device_kind, count).  A phase that raises is reported under
``<phase>_error`` AND makes the process exit non-zero; a device whose
peak is not in ``_PEAK_TFLOPS`` is an error, not a default.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

# bf16 peak by chip generation, keyed by ``device_kind`` prefix (Google
# Cloud TPU documentation, per-chip peak compute)
_PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


def _peak_tflops() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    for prefix, peak in _PEAK_TFLOPS.items():
        if kind.startswith(prefix):
            return peak
    raise RuntimeError(
        f"bench: no peak FLOP/s on record for device_kind {kind!r} "
        f"(platform {jax.devices()[0].platform!r}) — an MFU needs a known "
        f"chip; known: {sorted(_PEAK_TFLOPS)}")


def _device() -> dict:
    """The device every result line names, as JAX reports it."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def bench_llama(iters):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.static.functionalize import build_train_step

    batch, seq = 16, 2048
    # GQA config (G=4, llama-3-style grouping): the r4 flash kernels consume
    # kv heads natively — KV HBM traffic is 1/G of an expanded-heads kernel
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=4,
        max_position_embeddings=seq, dtype="bfloat16", recompute=True,
        loss_chunk_size=8192, recompute_layers=7,
        # rl7: the r5 rms-norm custom vjp freed ~4.3 GB of f32 residuals
        # (16 x [B,L,H] f32) re-opening rl8 (r4 optimum was rl10; rl<=8
        # OOMed then), and the fused-RoPE/delta kernels shaved the live
        # set enough for rl7 to edge rl8 (2x ~8 ms A/B; rl4 still OOMs)
    )
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01, moment_dtype="int8")
    step = build_train_step(model, None, opt)

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)), dtype="int64"
    )
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)), dtype="int64"
    )

    step(ids, labels).numpy()  # compile + warm up
    step(ids, labels).numpy()

    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(ids, labels)
    loss.numpy()  # sync: the last loss depends on every step before it
    dt = (time.perf_counter() - t0) / iters
    tokens_per_sec = batch * seq / dt

    # analytic model FLOPs: 6N per token for the matmuls + causal attention
    # (12*L*h*seq full-attention halved for the causal triangle); remat
    # recompute FLOPs are deliberately NOT counted — MFU is model FLOPs
    flops_per_token = (6 * n_params
                       + 6 * cfg.num_hidden_layers * cfg.hidden_size * seq)
    achieved_tflops = flops_per_token * tokens_per_sec / 1e12
    mfu = achieved_tflops / _peak_tflops()
    return {
        "mfu": mfu,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "achieved_tflops": round(achieved_tflops, 1),
        "params_b": round(n_params / 1e9, 3),
        "step_ms": round(dt * 1000, 1),
    }


def bench_resnet50(iters=10, batch=128):
    """ResNet-50 training images/sec (BASELINE.md vision north star)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.static.functionalize import build_train_step
    from paddle_tpu.vision.models import resnet50

    model = resnet50(num_classes=1000)
    model.to(dtype="bfloat16")
    opt = paddle.optimizer.Momentum(
        learning_rate=0.1, momentum=0.9, parameters=model.parameters(),
        weight_decay=1e-4)
    step = build_train_step(model, nn.CrossEntropyLoss(), opt)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(
        rng.standard_normal((batch, 3, 224, 224), dtype=np.float32)
        .astype(np.float32)).astype("bfloat16")
    y = paddle.to_tensor(rng.integers(0, 1000, (batch,)), dtype="int64")
    step(x, y).numpy()
    step(x, y).numpy()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(x, y)
    loss.numpy()
    dt = (time.perf_counter() - t0) / iters
    # conv MFU: ResNet-50 forward ≈ 4.089 GFLOPs per 224x224 image (the
    # standard multiply-add-counted-as-2 figure); training ≈ 3x forward
    train_flops_per_img = 3 * 4.089e9
    conv_mfu = train_flops_per_img * (batch / dt) / 1e12 / _peak_tflops()
    return {"resnet50_img_per_sec": round(batch / dt, 1),
            "resnet50_conv_mfu": round(conv_mfu, 4),
            "resnet50_step_ms": round(dt * 1000, 1)}


def bench_decode(ctx=2048, new_tokens=64):
    """Incremental decode tokens/sec over a static KV cache (VERDICT r4
    next-round #6 — the inference half of the LLM story).  Greedy-decodes
    ``new_tokens`` after a ``ctx - new_tokens`` prompt on the flagship bench
    config at batch 1 and 8; the whole loop (prefill + lax.scan decode +
    argmax) is ONE compiled program (models/llama_decode.py), so the number
    measures the chip, not the host dispatch path."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.llama_decode import decode_greedy

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=4,
        max_position_embeddings=ctx, dtype="bfloat16",
    )
    model = LlamaForCausalLM(cfg)
    model.eval()
    prompt = ctx - new_tokens
    rng = np.random.default_rng(0)
    out = {}
    for batch in (1, 8):
        ids = paddle.to_tensor(
            rng.integers(0, cfg.vocab_size, (batch, prompt)), dtype="int64")
        # warm (compile); a short and a long call so the decode-only rate
        # can be separated from the one-off prefill
        np.asarray(decode_greedy(model, ids, max_new_tokens=4, max_len=ctx))
        np.asarray(decode_greedy(model, ids, max_new_tokens=new_tokens,
                                 max_len=ctx))
        t0 = time.perf_counter()
        np.asarray(decode_greedy(model, ids, max_new_tokens=4, max_len=ctx))
        t_short = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(decode_greedy(model, ids, max_new_tokens=new_tokens,
                                 max_len=ctx))
        t_long = time.perf_counter() - t0
        per_tok = (t_long - t_short) / (new_tokens - 4)
        out[f"decode_tok_per_sec_b{batch}"] = round(batch / per_tok, 1)
    out["decode_ctx"] = ctx

    # lossless speculative decoding with model-free prompt-lookup drafting
    # (r5 exceed item): repetitive prompt = the lookup-friendly regime.
    # Greedy comparator measured on the SAME prompt/shape.
    from paddle_tpu.models.llama_decode import decode_speculative

    rep = paddle.to_tensor(
        np.tile(rng.integers(0, cfg.vocab_size, (1, 32)), (1, 8)),
        dtype="int64")
    spec_new, k = 128, 8
    lmax = 256 + spec_new + k + 2
    # warm both variants, then median of >=3 timed runs each — a single
    # timed run per variant made the A/B a 1-sample baseline (ADVICE r5);
    # bench_llama/bench_longseq already loop-and-aggregate
    np.asarray(decode_greedy(model, rep, max_new_tokens=spec_new,
                             max_len=lmax))
    np.asarray(decode_speculative(model, None, rep, max_new_tokens=spec_new,
                                  max_len=lmax, spec_k=k))
    tg, ts = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(decode_greedy(model, rep, max_new_tokens=spec_new,
                                 max_len=lmax))
        tg.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        np.asarray(decode_speculative(model, None, rep,
                                      max_new_tokens=spec_new,
                                      max_len=lmax, spec_k=k))
        ts.append(time.perf_counter() - t0)
    t_greedy, t_spec = float(np.median(tg)), float(np.median(ts))
    out["decode_spec_ngram_tok_per_sec"] = round(spec_new / t_spec, 1)
    out["decode_spec_ngram_speedup"] = round(t_greedy / t_spec, 2)
    return out


def bench_serving(n_requests=64, batch=8):
    """Continuous-batching serving A/B on a mixed-length workload: request
    throughput and per-request latency of the iteration-level scheduler
    (paddle_tpu/serving) against the run-to-completion "gang" baseline.
    64 requests, prompts uniform 64-1024, outputs log-uniform 128-512
    (serving output lengths are heavy-tailed; the gang baseline's waste is
    the per-batch max-vs-mean gap, so a uniform draw would understate the
    realistic regime), fixed batch 8.  Three runs: continuous-greedy vs
    gang-greedy shares the SAME compiled step programs, so
    ``serving_speedup`` is the pure scheduling win; continuous-spec
    (prompt-lookup speculative, lossless) vs the same gang-greedy baseline
    is the full engine win ``serving_spec_speedup`` — scheduling composed
    with speculation.  Prompts are tiled 32-token segments (the
    lookup-friendly regime, matching the decode_spec row; greedy cost is
    content-independent so the scheduling A/B is unaffected).

    Latency columns come FROM THE METRICS REGISTRY (paddle_tpu/
    observability): each run feeds a private registry, and TTFT/TPOT
    p50/p95 are read back off the engine's own log2-bucketed histograms —
    the same series a production scrape would see, so the bench exercises
    the observability path end-to-end (bucket-interpolated percentiles,
    accurate to within one log2 bucket).

    Round 15 adds the int8-KV A/B (``kv_dtype="int8"``, quantize-on-append
    / dequant-in-loop): the standard workload on the quantized cache vs
    the same continuous-greedy baseline — ``serving_q8_speedup`` (ratio-
    only off-chip: the CPU host pays the dequant multiplies without the
    HBM-bandwidth win they buy on chip), two drift columns — the lossy
    knob's quality cost — and the KV-only analytic traffic pair.  Drift
    is reported two ways because greedy decoding cascades: once one
    near-tied argmax flips, the streams explore different continuations
    and every later position counts as a mismatch, so
    ``serving_q8_greedy_drift`` (aligned-position mismatch fraction) is
    an upper bound inflated by divergence, while
    ``serving_q8_flip_per_tok`` (first divergences over tokens compared
    up to each stream's first divergence) is the per-token probability
    that quantization flips a pick — the number the quality budget is
    declared on.  On the random-init bench-small model argmax margins
    are artificially thin, so both read high relative to a trained
    model; the tests' parity matrix (tests/test_serving_q8.py, trained-
    margin-free but wide-margin f32 tiny model) observes drift 0.0.
    The KV analytic pair:
    bytes-per-context-token pair the acceptance gate compares —
    ``serving_hbm_gb_per_tok_q8`` (int8 data + f16 per-(position, head)
    scale: D+2 bytes per head-row) vs ``serving_hbm_gb_per_tok_kv_bf16``
    (2D bytes at the production serving dtype), a fixed geometric ratio
    of (D+2)/(2D) ~ 0.53 at D=32.

    Round 9 adds two engine A/Bs on the same compiled-program family:
    ``serving_chunked_speedup`` (length-adaptive chunked cache reads,
    decode_chunk=256, vs the full [B, Lmax] masked read) and
    ``serving_pipeline_speedup`` (double-buffered dispatch vs the
    synchronous loop), plus an analytic achieved-HBM estimate
    (``serving_hbm_gb_per_tok_*`` — param bytes amortized over the batch +
    per-slot KV bytes at the read length; ``serving_hbm_gbps_est_*`` scales
    it by measured tok/s) and a low-occupancy split
    (``serving_low_occ_*``: short contexts in the same Lmax=2048 cache —
    the regime where chunked reads win big; the standard mixed workload
    doubles as the full-occupancy column, where the requirement is merely
    no regression).

    Round 10 adds the chunked-prefill A/B on a long-prompt-heavy mix
    (prompts at the top of the bucket range, modest outputs — admissions
    keep landing while residents decode): ``serving_chunked_prefill_speedup``
    (budgeted chunk interleaving vs the monolithic per-bucket prefill),
    ``serving_adm_tpot_p95_ms_{monolithic,chunked}`` (p95 of
    ``serving_tpot_during_admission_seconds`` — decode interference while
    admission work is in flight, the stall the chunking exists to bound),
    and ``serving_prefill_programs_{monolithic,chunked}`` (one program per
    touched bucket before — the A/B-run trace delta — vs the process-wide
    chunked total after: O(1) regardless of prompt lengths served — read
    off the llama_decode CompileCacheMonitor).

    Round 11 adds the tensor-parallel A/B (serving/sharding.py): the same
    model mesh-placed across ``serving_tp_devices`` host devices vs the
    single-device engine (``serving_tp_speedup`` — on the CPU host mesh
    this is a ratio-only smoke column: host collectives cost more than
    they parallelize, the capacity win is the point), plus the per-shard
    analytic ``serving_hbm_gb_per_tok_tp`` (replicated params in full +
    sharded params and head-sharded KV reads at 1/N — the per-chip
    bytes/token the placement buys).  The row needs >1 host device, so
    the device-count forcing at the top of this function must run before
    jax initializes its backend; when it loses that race the TP columns
    report the single-device fallback instead of failing the bench.

    Round 12 adds the degraded-mode smoke (the reliability layer,
    serving/faults.py): the same mixed workload under a seeded FaultPlan
    (5% transient dispatch faults retried with backoff, two poison
    requests quarantined off the batch, deadlines on ~10% of traffic) and
    a bounded admission queue the submit loop backpressures against —
    ``serving_degraded_tok_per_sec`` (goodput: tokens of requests that
    finished ``done``), ``serving_degraded_goodput_ratio`` (vs the clean
    continuous run), and the terminal counts
    (``serving_degraded_{shed,timed_out,poisoned,retries}``) read off the
    engine's own reliability counters.  The column the row exists for is
    the ratio: injected faults must degrade throughput proportionally —
    never collapse it.

    Round 13 adds the request-lifecycle observability tripwire:
    ``serving_recorder_overhead_pct`` (the standard continuous run with
    the flight recorder + request timelines on — the default — vs
    ``recorder=False``; pure host bookkeeping, so the expected value is
    measurement noise) and a ``metrics`` key carrying the continuous
    run's full ``MetricsRegistry.snapshot()`` so every BENCH_r*.json row
    records the series (phase histograms, SLO attainment, reliability
    counters) its headline numbers were derived from.

    Round 19 adds the fused-prefill A/B (ops/prefill_attention_pallas.py,
    keyed through the serving/program_key.py registry):
    ``serving_fused_prefill_speedup`` (the reference chunked
    read + quantize-append vs the single fused kernel on the long-prompt
    paged-int8 workload; ratio-only off-chip, where the kernel runs
    under interpret emulation), ``serving_adm_tpot_p95_ms_{unfused,fused}``
    (round 10's admission-interference p95 for both arms), and the TP
    row gains ``serving_tp_overlap_speedup`` (the same mesh run with
    each layer's row-parallel psum split into two overlapped segments —
    byte-identical math, ratio-only on the host mesh)."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import MetricsRegistry
    from paddle_tpu.serving import (EngineOverloaded, FaultPlan, Request,
                                    ServingEngine)

    import jax

    # BENCH_SERVING_SMALL=1 shrinks the model + workload to a CPU-feasible
    # scale (same scheduler, same compiled-program family, same A/B
    # structure) — for smoke runs and ratio-only columns off-chip; the
    # driver's on-chip run uses the full configuration below.
    small = os.environ.get("BENCH_SERVING_SMALL") == "1"
    if small:
        n_requests, batch, lmax = min(n_requests, 16), 4, 512
        cfg = LlamaConfig(
            vocab_size=2048, hidden_size=256, intermediate_size=688,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=2, max_position_embeddings=lmax,
            dtype="float32",
        )
        p_lo, p_hi, o_lo, o_hi = 32, 257, 32, 128
    else:
        lmax = 2048
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=lmax,
            dtype="bfloat16",
        )
        p_lo, p_hi, o_lo, o_hi = 64, 1025, 128, 512
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    plens = rng.integers(p_lo, p_hi, n_requests)
    olens = np.rint(np.exp(
        rng.uniform(np.log(o_lo), np.log(o_hi), n_requests))).astype(np.int64)
    prompts = [np.tile(rng.integers(0, cfg.vocab_size, 32), p // 32 + 1)[:p]
               for p in plens]
    total_new = int(olens.sum())

    def run(policy, mode, reqs=None, m=None, **ekw):
        reg = MetricsRegistry()  # isolated per run: clean percentiles
        eng = ServingEngine(m if m is not None else model,
                            batch_size=batch, max_len=lmax,
                            mode=mode, sync_every=4, spec_k=8, policy=policy,
                            registry=reg, **ekw)
        for p, o in (reqs if reqs is not None else zip(prompts, olens)):
            eng.submit(Request(p, int(o)))
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
        lats = np.array([r.t_done - t0 for r in done])
        return dt, lats, reg

    def lat_cols(reg, policy, prefix):
        cols = {}
        for series, key in (("serving_ttft_seconds", "ttft"),
                            ("serving_tpot_seconds", "tpot")):
            h = reg.get(series).labels(policy=policy)
            for p in (50, 95):
                cols[f"{prefix}_{key}_p{p}_ms"] = round(
                    h.percentile(p) * 1e3, 1)
        return cols

    # analytic HBM bytes per decoded token: the whole weight set is read
    # once per step and amortized over the batch, plus every slot's KV read
    # at the path's read length (Lmax for the full masked read, ~the mean
    # live context for the chunked read — the trip count tracks the batch
    # max, so this is the optimistic end of the estimate)
    from paddle_tpu.models.llama_decode import _decode_params_of
    import jax as _jax
    params, _ = _decode_params_of(model, lmax)
    param_bytes = sum(l.size * l.dtype.itemsize
                      for l in _jax.tree_util.tree_leaves(params))
    kv_itemsize = 4 if cfg.dtype == "float32" else 2
    kv_row = cfg.num_hidden_layers * 2 * cfg.num_key_value_heads * \
        (cfg.hidden_size // cfg.num_attention_heads) * kv_itemsize

    def hbm_gb_per_tok(read_len):
        return (param_bytes / batch + kv_row * read_len) / 1e9

    run("continuous", "greedy")  # warm: every prefill bucket + the step
    dt_c, lats_c, reg_c = run("continuous", "greedy")
    dt_g, lats_g, reg_g = run("gang", "greedy")
    # A/B 6 (round 13) — flight-recorder overhead: the same continuous run
    # with the event ring + request timelines disabled.  The recorder is
    # pure host bookkeeping (lock + deque append per event), so this
    # column is a regression tripwire expected to sit at measurement
    # noise; a visible cost here means something started syncing.
    dt_r, _, _ = run("continuous", "greedy", recorder=False)
    # A/B 1 — chunked vs full cache read (same scheduler, same programs
    # otherwise): decode_chunk=None restores the full [B, Lmax] masked read
    run("continuous", "greedy", decode_chunk=None)  # warm the full-read step
    dt_f, _, _ = run("continuous", "greedy", decode_chunk=None)
    # A/B 2 — pipelined vs synchronous dispatch (same chunked step)
    dt_y, _, _ = run("continuous", "greedy", pipeline=False)
    # low-occupancy split: short contexts in the SAME Lmax cache
    lo_n = max(8, n_requests // 2)
    lo_p = rng.integers(lmax // 32, lmax // 16 + 1, lo_n)
    lo_o = rng.integers(lmax // 64, lmax // 32 + 1, lo_n)
    lo_reqs = [(np.tile(rng.integers(0, cfg.vocab_size, 32),
                        p // 32 + 1)[:p], o) for p, o in zip(lo_p, lo_o)]
    lo_new = int(lo_o.sum())
    run("continuous", "greedy", reqs=list(lo_reqs))  # warm the 64/128 buckets
    dt_lc, _, _ = run("continuous", "greedy", reqs=list(lo_reqs))
    dt_lf, _, _ = run("continuous", "greedy", reqs=list(lo_reqs),
                      decode_chunk=None)
    # A/B 3 (round 10) — chunked prefill vs monolithic per-bucket prefill
    # on a long-prompt-heavy mix; program counts are trace-count deltas
    from paddle_tpu.models.llama_decode import _mon as _dec_mon
    lp_n = max(8, n_requests // 2)
    lp_p = rng.integers(max(p_lo, int(p_hi * 0.6)), p_hi, lp_n)
    lp_o = rng.integers(o_lo, max(o_lo + 1, o_hi // 2), lp_n)
    lp_reqs = [(np.tile(rng.integers(0, cfg.vocab_size, 32),
                        p // 32 + 1)[:p], o) for p, o in zip(lp_p, lp_o)]
    pchunk = 64 if small else 256

    def adm_tpot_p95_ms(reg):
        h = reg.get("serving_tpot_during_admission_seconds").labels(
            policy="continuous")
        return round(h.percentile(95) * 1e3, 1) if h.count else None

    def traces(key):
        return _dec_mon.trace_counts().get(key, 0)

    mono0 = traces("serving_prefill_slot")
    run("continuous", "greedy", reqs=list(lp_reqs), prefill_chunk=None)
    dt_mp, _, reg_mp = run("continuous", "greedy", reqs=list(lp_reqs),
                           prefill_chunk=None)
    mono_programs = traces("serving_prefill_slot") - mono0
    run("continuous", "greedy", reqs=list(lp_reqs), prefill_chunk=pchunk)
    dt_cp, _, reg_cp = run("continuous", "greedy", reqs=list(lp_reqs),
                           prefill_chunk=pchunk)
    # process-wide total: EVERY chunked run in this bench, across every
    # distinct prompt length served, compiled this many prefill programs
    # (one per static config — chunk width x spec-mode hist; the
    # monolithic delta above is one per touched bucket for the A/B
    # workload alone)
    chunk_programs = traces("serving_prefill_chunk")
    # A/B 4 (round 11) — tensor-parallel mesh placement vs single device
    # (serving/sharding.py): same workload, same scheduler; the small
    # config's nkv=2 is bumped to 4 so the KV heads divide the mesh axis
    n_tp = 4
    tp_cols = {"serving_tp_devices": 1}
    if len(jax.devices()) >= n_tp:
        import dataclasses

        from jax.sharding import Mesh, PartitionSpec as _PS

        from paddle_tpu.serving.sharding import (llama_tp_rules,
                                                 match_partition_rules)
        tp_cfg = cfg if cfg.num_key_value_heads % n_tp == 0 else \
            dataclasses.replace(cfg, num_key_value_heads=4)
        tp_model = model if tp_cfg is cfg else LlamaForCausalLM(tp_cfg)
        tp_model.eval()
        mesh = Mesh(np.array(jax.devices()[:n_tp]), ("mp",))
        run("continuous", "greedy", m=tp_model)              # warm 1-dev
        dt_t1, _, _ = run("continuous", "greedy", m=tp_model)
        run("continuous", "greedy", m=tp_model, mesh=mesh)   # warm mesh
        dt_tn, _, _ = run("continuous", "greedy", m=tp_model, mesh=mesh)
        # round 19 — overlapped row-parallel psum: the same mesh run with
        # each layer's output-feature reduction split into 2 segments so
        # the collective overlaps the remaining matmul work.  Host
        # collectives don't overlap, so off-chip this is a ratio-only
        # smoke column (byte-identical math is pinned by
        # tests/test_serving_prefill_fused.py)
        run("continuous", "greedy", m=tp_model, mesh=mesh, tp_overlap=2)
        dt_to, _, _ = run("continuous", "greedy", m=tp_model, mesh=mesh,
                          tp_overlap=2)
        # per-shard analytic bytes/token: replicated params read in full
        # on every chip, sharded params and the head-sharded KV at 1/N
        tp_params, _ = _decode_params_of(tp_model, lmax)
        tp_specs = match_partition_rules(llama_tp_rules(), tp_params)
        repl_b = shard_b = 0
        for leaf, spec in zip(
                _jax.tree_util.tree_leaves(tp_params),
                _jax.tree_util.tree_leaves(
                    tp_specs, is_leaf=lambda x: isinstance(x, _PS))):
            b = leaf.size * leaf.dtype.itemsize
            if any(ax is not None for ax in spec):
                shard_b += b
            else:
                repl_b += b
        tp_kv_row = tp_cfg.num_hidden_layers * 2 * \
            tp_cfg.num_key_value_heads * \
            (tp_cfg.hidden_size // tp_cfg.num_attention_heads) * kv_itemsize
        tp_cols = {
            "serving_tp_devices": n_tp,
            "serving_tp_speedup": round(dt_t1 / dt_tn, 2),
            "serving_tp_tok_per_sec": round(total_new / dt_tn, 1),
            "serving_hbm_gb_per_tok_tp": round(
                ((repl_b + shard_b / n_tp) / batch
                 + tp_kv_row * float(np.mean(plens + olens / 2)) / n_tp)
                / 1e9, 4),
            "serving_tp_overlap_speedup": round(dt_tn / dt_to, 2),
        }
    # A/B 5 (round 12) — degraded-mode smoke: the standard workload under
    # a seeded fault plan + bounded queue; goodput counts only requests
    # that finished "done" (shed/timed_out/poisoned traffic is the cost
    # being measured, not throughput)
    fplan = FaultPlan(seed=12, dispatch_error_rate=0.05,
                      poison={1: 8, 5: 24})
    reg_fb = MetricsRegistry()
    eng_fb = ServingEngine(model, batch_size=batch, max_len=lmax,
                           mode="greedy", sync_every=4, registry=reg_fb,
                           max_pending=2 * batch, retry_backoff=1e-3,
                           faults=fplan)
    fb_deadline = 500 if small else 30_000
    shed_n = 0
    t0 = time.perf_counter()
    for i, (p, o) in enumerate(zip(prompts, olens)):
        dl = fb_deadline if i % 10 == 0 else None
        while True:
            try:
                eng_fb.submit(Request(p, int(o), rid=i, deadline_ms=dl))
                break
            except EngineOverloaded:
                # client backpressure: spend a step to drain the queue,
                # then resubmit — each rejection is one shed
                shed_n += 1
                eng_fb.step()
    fb_statuses = eng_fb.drain()
    dt_fb = time.perf_counter() - t0
    good_tok = sum(len(r.output_ids) for r in eng_fb._finished
                   if r.status == "done")

    def _rel(series):
        return int(reg_fb.get(series).labels(policy="continuous").value)

    # A/B 7 (round 15) — int8 KV quantization: same workload, quantized
    # cache.  Token streams are captured on both sides so the drift
    # column measures the knob's quality cost, not just its speed.
    def run_tok(**ekw):
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=batch, max_len=lmax,
                            mode="greedy", sync_every=4, registry=reg,
                            **ekw)
        rs = [eng.submit(Request(p, int(o)))
              for p, o in zip(prompts, olens)]
        t0 = time.perf_counter()
        eng.run()
        return time.perf_counter() - t0, [list(r.output_ids) for r in rs]

    _, ref_toks = run_tok()              # warm programs: reference tokens
    run_tok(kv_dtype="int8")             # warm the q8 program family
    dt_q8, q8_toks = run_tok(kv_dtype="int8")
    q8_drift_n = sum(sum(x != y for x, y in zip(a, b))
                     for a, b in zip(ref_toks, q8_toks))
    # per-token flip (hazard) rate: count each stream's FIRST divergence
    # over the tokens compared up to it — immune to cascade inflation
    q8_div = q8_cmp = 0
    for a, b in zip(ref_toks, q8_toks):
        k = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if k is None:
            q8_cmp += len(a)
        else:
            q8_div += 1
            q8_cmp += k + 1
    hd = cfg.hidden_size // cfg.num_attention_heads
    kv_tok_bf16 = cfg.num_hidden_layers * 2 * cfg.num_key_value_heads \
        * hd * 2
    kv_tok_q8 = cfg.num_hidden_layers * 2 * cfg.num_key_value_heads \
        * (hd + 2)

    # A/B 8 (round 16) — fused pallas decode read and int8 decode
    # weights, each against the same continuous-greedy baseline.  Off
    # the chip both kernels run under interpret/dequant emulation, so
    # only the ratio columns carry cross-round meaning; the drift
    # columns are the quality cost on the same captured token streams.
    run_tok(attn_impl="pallas")          # warm the fused program family
    dt_fa, fa_toks = run_tok(attn_impl="pallas")
    fa_drift_n = sum(sum(x != y for x, y in zip(a, b))
                     for a, b in zip(ref_toks, fa_toks))
    run_tok(weight_dtype="int8")         # warm the w8 program family
    dt_w8, w8_toks = run_tok(weight_dtype="int8")
    w8_drift_n = sum(sum(x != y for x, y in zip(a, b))
                     for a, b in zip(ref_toks, w8_toks))
    # analytic per-token decode-weight traffic: every step reads the
    # whole projection/MLP weight set once, amortized over the batch;
    # bf16 is the production storage dtype, int8 adds one f16 scale per
    # output channel
    kvd = cfg.num_key_value_heads * hd
    h, inter = cfg.hidden_size, cfg.intermediate_size
    w_shapes = [(h, h), (h, kvd), (h, kvd), (h, h),
                (h, inter), (h, inter), (inter, h)]
    w_elems = cfg.num_hidden_layers * sum(a * b for a, b in w_shapes)
    w_scales = cfg.num_hidden_layers * sum(b for _, b in w_shapes)
    w_tok_bf16 = w_elems * 2 / batch
    w_tok_w8 = (w_elems + 2 * w_scales) / batch

    # A/B 9 (round 19) — fused chunked-prefill kernel
    # (ops/prefill_attention_pallas.py): the long-prompt chunked-admission
    # workload (A/B 3's lp_reqs) on a paged int8 pool with
    # prefill_chunk == kv_block == decode_chunk so the fused path's
    # alignment contract holds for every admission chunk.
    # prefill_impl=None is the reference chunked read + quantize-append;
    # "pallas" fuses the causal-masked chunk attention WITH the int8
    # quantize-on-append into one kernel launch.  Off the chip the kernel
    # runs under interpret emulation, so only the ratio carries
    # cross-round meaning; the admission-interference p95 (the round-10
    # metric) rides along for both arms — the fused kernel must not give
    # back the stall-free admission chunking bought.
    fp_kw = dict(reqs=list(lp_reqs), prefill_chunk=pchunk,
                 decode_chunk=pchunk, kv_block=pchunk,
                 max_live_tokens=batch * lmax, kv_dtype="int8")
    run("continuous", "greedy", **fp_kw)             # warm reference arm
    dt_pu, _, reg_pu = run("continuous", "greedy", **fp_kw)
    run("continuous", "greedy", prefill_impl="pallas", **fp_kw)
    dt_pf, _, reg_pf = run("continuous", "greedy",
                           prefill_impl="pallas", **fp_kw)

    run("continuous", "spec")    # warm the spec step
    dt_s, _, reg_s = run("continuous", "spec")
    spec_child = reg_s.get("serving_spec_accept_rate").labels(
        policy="continuous", source="prompt_lookup")
    # A/B 10 (round 23) — resident-draft-model speculation (the draft
    # forward replaces prompt-lookup as the candidate source; emission
    # still comes only from the verify forward's own greedy picks, so
    # both arms stay lossless).  Off the chip the draft forward runs at
    # host speed next to the target, so the speedup columns are
    # ratio-only; the accept-rate columns are REAL — counted off the
    # verify comparison.  Two drafters: ``dm`` is the quarter-depth
    # shrunk model (realistic shape; random-init, so its acceptance
    # reflects draft/target agreement on the bench model, NOT a trained
    # pair — expect near-chance), ``dm_self`` is the target drafting for
    # itself (acceptance ~1.0 by construction — the upper bound, and the
    # proof the acceptance plumbing measures agreement rather than
    # asserting it).  The self-draft arm runs on a PAGED pool so the
    # draft tenant's accounting rides the bench: the leak column reads
    # the draft tenant's block gauge after drain and must be 0.
    from paddle_tpu.serving.engine import SpecConfig
    dcfg_kw = dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=max(1, cfg.num_hidden_layers // 4),
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        max_position_embeddings=lmax, dtype=cfg.dtype)
    draft = LlamaForCausalLM(LlamaConfig(**dcfg_kw))
    draft.eval()
    dm_spec = SpecConfig(source="draft_model", draft_model=draft)
    run("continuous", "spec", spec=dm_spec)      # warm the draft programs
    dt_dm, _, reg_dm = run("continuous", "spec", spec=dm_spec)
    dm_child = reg_dm.get("serving_spec_accept_rate").labels(
        policy="continuous", source="draft_model")
    self_spec = SpecConfig(source="draft_model", draft_model=model)
    sp_kw = dict(kv_block=pchunk, prefill_chunk=pchunk,
                 max_live_tokens=2 * batch * lmax)
    run("continuous", "spec", spec=self_spec, **sp_kw)
    dt_ds, _, reg_ds = run("continuous", "spec", spec=self_spec, **sp_kw)
    ds_child = reg_ds.get("serving_spec_accept_rate").labels(
        policy="continuous", source="draft_model")
    dm_leaked = reg_ds.get("serving_kv_blocks_used").labels(
        policy="continuous", model="draft")
    stall = reg_c.get("serving_pipeline_stall_seconds").labels(
        policy="continuous")
    ctx_full = float(np.mean(plens + olens / 2))
    ctx_lo = float(np.mean(lo_p + lo_o / 2))
    return {
        **lat_cols(reg_c, "continuous", "serving"),
        **lat_cols(reg_g, "gang", "serving_baseline"),
        "serving_spec_accept_rate": round(spec_child.value, 3),
        "serving_req_per_sec": round(n_requests / dt_c, 2),
        "serving_tok_per_sec": round(total_new / dt_c, 1),
        "serving_p50_ms": round(float(np.percentile(lats_c, 50)) * 1e3, 1),
        "serving_p95_ms": round(float(np.percentile(lats_c, 95)) * 1e3, 1),
        "serving_baseline_req_per_sec": round(n_requests / dt_g, 2),
        "serving_baseline_p50_ms": round(
            float(np.percentile(lats_g, 50)) * 1e3, 1),
        "serving_baseline_p95_ms": round(
            float(np.percentile(lats_g, 95)) * 1e3, 1),
        "serving_speedup": round(dt_g / dt_c, 2),
        "serving_spec_tok_per_sec": round(total_new / dt_s, 1),
        "serving_spec_speedup": round(dt_g / dt_s, 2),
        # resident-draft-model arms (round 23): speedups ratio-only
        # off-chip, accept rates real (see A/B 10 comment)
        "serving_spec_dm_accept_rate": round(dm_child.value, 3),
        "serving_spec_dm_tok_per_sec": round(total_new / dt_dm, 1),
        "serving_spec_dm_speedup": round(dt_g / dt_dm, 2),
        "serving_spec_dm_self_accept_rate": round(ds_child.value, 3),
        "serving_spec_dm_self_tok_per_sec": round(total_new / dt_ds, 1),
        "serving_spec_dm_self_speedup": round(dt_g / dt_ds, 2),
        "serving_spec_dm_draft_blocks_leaked": int(dm_leaked.value),
        # chunked-vs-full and pipelined-vs-sync A/Bs (round 9)
        "serving_chunked_speedup": round(dt_f / dt_c, 2),
        "serving_pipeline_speedup": round(dt_y / dt_c, 2),
        "serving_pipeline_stall_p50_ms": round(
            stall.percentile(50) * 1e3, 2),
        "serving_low_occ_tok_per_sec": round(lo_new / dt_lc, 1),
        "serving_low_occ_chunked_speedup": round(dt_lf / dt_lc, 2),
        # chunked-prefill A/B (round 10): stall-free admission
        "serving_chunked_prefill_speedup": round(dt_mp / dt_cp, 2),
        "serving_adm_tpot_p95_ms_monolithic": adm_tpot_p95_ms(reg_mp),
        "serving_adm_tpot_p95_ms_chunked": adm_tpot_p95_ms(reg_cp),
        "serving_prefill_programs_monolithic": mono_programs,
        "serving_prefill_programs_chunked": chunk_programs,
        # analytic achieved-HBM estimate: bytes a step MUST move per token
        # on each read path, and that figure scaled by the measured rate
        "serving_hbm_gb_per_tok_full": round(hbm_gb_per_tok(lmax), 4),
        "serving_hbm_gb_per_tok_chunked": round(
            hbm_gb_per_tok(ctx_full), 4),
        "serving_hbm_gbps_est_full": round(
            hbm_gb_per_tok(lmax) * (total_new / dt_f), 1),
        "serving_hbm_gbps_est_chunked": round(
            hbm_gb_per_tok(ctx_full) * (total_new / dt_c), 1),
        "serving_low_occ_hbm_gb_per_tok_chunked": round(
            hbm_gb_per_tok(ctx_lo), 4),
        # tensor-parallel A/B (round 11)
        **tp_cols,
        # degraded-mode smoke (round 12): goodput under injected faults
        "serving_degraded_tok_per_sec": round(good_tok / dt_fb, 1),
        "serving_degraded_goodput_ratio": round(
            (good_tok / dt_fb) / (total_new / dt_c), 2),
        "serving_degraded_done": sum(
            1 for s in fb_statuses.values() if s == "done"),
        "serving_degraded_shed": shed_n,
        "serving_degraded_timed_out": _rel(
            "serving_requests_timed_out_total"),
        "serving_degraded_poisoned": _rel(
            "serving_requests_poisoned_total"),
        "serving_degraded_retries": _rel(
            "serving_dispatch_retries_total"),
        # int8-KV A/B (round 15): the lossy knob's cost (drift) and the
        # analytic KV-traffic win it buys; the bf16 column is the
        # production serving dtype regardless of the bench model's own
        "serving_q8_tok_per_sec": round(total_new / dt_q8, 1),
        "serving_q8_speedup": round(dt_c / dt_q8, 2),
        "serving_q8_greedy_drift": round(q8_drift_n / total_new, 4),
        "serving_q8_flip_per_tok": round(q8_div / max(q8_cmp, 1), 4),
        "serving_hbm_gb_per_tok_kv_bf16": kv_tok_bf16 / 1e9,
        "serving_hbm_gb_per_tok_q8": kv_tok_q8 / 1e9,
        "serving_q8_kv_bytes_ratio": round(kv_tok_q8 / kv_tok_bf16, 4),
        # fused-kernel + int8-weight A/Bs (round 16): wall-clock ratios
        # vs the same baseline, drift on the same captured streams, and
        # the analytic weight-traffic win (bf16 baseline vs int8 data +
        # f16 per-output-channel scales)
        "serving_fused_attn_tok_per_sec": round(total_new / dt_fa, 1),
        "serving_fused_attn_speedup": round(dt_c / dt_fa, 2),
        "serving_fused_greedy_drift": round(fa_drift_n / total_new, 4),
        "serving_w8_tok_per_sec": round(total_new / dt_w8, 1),
        "serving_w8_speedup": round(dt_c / dt_w8, 2),
        "serving_w8_greedy_drift": round(w8_drift_n / total_new, 4),
        "serving_hbm_gb_per_tok_w_bf16": w_tok_bf16 / 1e9,
        "serving_hbm_gb_per_tok_w8": w_tok_w8 / 1e9,
        "serving_w8_bytes_ratio": round(w_tok_w8 / w_tok_bf16, 4),
        # fused-prefill A/B (round 19): wall-clock ratio on the
        # long-prompt paged-int8 workload, plus admission-interference
        # p95 for both arms (the fused kernel keeps decode TPOT bounded
        # while admissions stream through it)
        "serving_fused_prefill_speedup": round(dt_pu / dt_pf, 2),
        "serving_adm_tpot_p95_ms_unfused": adm_tpot_p95_ms(reg_pu),
        "serving_adm_tpot_p95_ms_fused": adm_tpot_p95_ms(reg_pf),
        # flight-recorder overhead (round 13): recorder-on (the default,
        # dt_c) vs recorder-off on the same warm programs
        "serving_recorder_overhead_pct": round(
            (dt_c - dt_r) / dt_r * 100.0, 2),
        # the continuous run's full registry snapshot rides along so each
        # BENCH_r*.json row carries the observability data the numbers
        # above were derived from (phase histograms, SLO gauges, counters)
        "metrics": reg_c.snapshot(),
    }


def bench_serving_paged(n_requests=64, batch=8):
    """Paged-KV A/B (round 14, serving/kv_cache.PagedKVCacheManager): a
    shared-prefix workload — every request opens with the same
    ``Lmax/2``-token system prompt plus a short unique suffix, the
    RAG/agent serving shape prefix caching exists for.

    Three measurements:

    * ``serving_paged_speedup`` / ``serving_prefix_cache_hit_rate`` —
      the paged engine (block pool sized to the SAME HBM as the dense
      engine's ``B x Lmax`` cache) vs the dense engine on the same
      workload and batch.  The hit rate is read off the engine's own
      counters (``serving_prefix_reuse_tokens_total`` over
      ``serving_prompt_tokens_total``); only the first admission wave
      can miss, so the shared-prefix shape must push it past 0.5.  On
      the CPU host the speedup is ratio-only smoke (the gather costs
      more than the skipped prefill saves at toy scale); on chip the
      skipped prefill FLOPs are the point.
    * ``serving_paged_peak_concurrent`` vs
      ``serving_fixed_hbm_dense_slots`` — the capacity claim: at a FIXED
      HBM budget of ``B_dense x Lmax`` cache tokens, the dense engine
      caps at ``B_dense`` concurrent requests by construction, while the
      paged engine (4x the slots, same pool) admits every request whose
      worst-case block budget fits — shared prefix blocks are counted
      once and suffixes are short, so strictly more requests run
      concurrently (``serving_paged_capacity_ratio`` > 1).
    * ``serving_live_token_util`` — mean of ``live_tokens / pool`` over
      the stepped capacity run: LOGICAL context tokens served per
      PHYSICAL pool token.  Values above 1.0 are the prefix-dedup win —
      shared blocks are stored once but serve every slot that maps them
      — where the dense engine is hard-capped at ``mean_ctx / Lmax``
      (each row private, most of it stranded padding).
    """
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import MetricsRegistry
    from paddle_tpu.serving import Request, ServingEngine

    small = os.environ.get("BENCH_SERVING_SMALL") == "1"
    if small:
        n_requests, batch, lmax, kvb = min(n_requests, 32), 4, 512, 64
        cfg = LlamaConfig(
            vocab_size=2048, hidden_size=256, intermediate_size=688,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=2, max_position_embeddings=lmax,
            dtype="float32",
        )
        o_lo, o_hi = 24, 49
    else:
        lmax, kvb = 2048, 256
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=lmax,
            dtype="bfloat16",
        )
        o_lo, o_hi = 64, 129
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(14)
    prefix = rng.integers(0, cfg.vocab_size, lmax // 2)
    sfx_lens = rng.integers(kvb // 2, kvb + 1, n_requests)
    prompts = [np.concatenate([prefix,
                               rng.integers(0, cfg.vocab_size, int(s))])
               for s in sfx_lens]
    olens = rng.integers(o_lo, o_hi, n_requests)
    total_new = int(olens.sum())

    def mk(pool=None, b=batch, reg=None):
        # the default bucket ladder tops out at Lmax/2 — the prefix-heavy
        # prompts need one more rung (buckets only shape prefill padding;
        # the chunked path dispatches per kvb-chunk regardless)
        kw = dict(batch_size=b, max_len=lmax, sync_every=4,
                  decode_chunk=kvb, prefill_chunk=kvb, registry=reg,
                  prompt_buckets=[lmax // 8, lmax // 4, lmax // 2,
                                  3 * lmax // 4],
                  instrument=reg is not None, recorder=False)
        if pool is not None:
            kw.update(kv_block=kvb, max_live_tokens=pool)
        return ServingEngine(model, **kw)

    def run(eng):
        for p, o in zip(prompts, olens):
            eng.submit(Request(p, int(o)))
        t0 = time.perf_counter()
        eng.run()
        return time.perf_counter() - t0

    # A/B 1 — same batch, same HBM (pool = B x Lmax): dense vs paged
    run(mk())                      # warm the dense programs
    dt_dense = run(mk())
    run(mk(pool=batch * lmax))     # warm the paged programs
    reg_p = MetricsRegistry()
    dt_paged = run(mk(pool=batch * lmax, reg=reg_p))
    lbl = dict(policy="continuous")
    reuse = reg_p.get("serving_prefix_reuse_tokens_total"
                      ).labels(**lbl).value
    prompt_tok = reg_p.get("serving_prompt_tokens_total"
                           ).labels(**lbl).value

    # A/B 2 — capacity at FIXED HBM: pool = B_dense x Lmax tokens, 4x the
    # slots; step manually to observe peak concurrency and pool loading
    b_dense = max(2, batch // 2)
    pool = b_dense * lmax
    eng = mk(pool=pool, b=min(4 * b_dense, n_requests))
    for p, o in zip(prompts, olens):
        eng.submit(Request(p, int(o)))
    peak, util = 0, []
    while eng.has_work:
        eng.step()
        peak = max(peak, eng._kv.occupied())
        util.append(eng._kv.live_tokens() / pool)

    return {
        "serving_paged_kv_block": kvb,
        "serving_paged_speedup": round(dt_dense / dt_paged, 2),
        "serving_paged_tok_per_sec": round(total_new / dt_paged, 1),
        "serving_prefix_cache_hit_rate": round(reuse / prompt_tok, 3),
        "serving_fixed_hbm_dense_slots": b_dense,
        "serving_paged_peak_concurrent": int(peak),
        "serving_paged_capacity_ratio": round(peak / b_dense, 2),
        "serving_live_token_util": round(float(np.mean(util)), 3),
    }


def bench_serving_tiered(n_families=12, waves=3, batch=2):
    """Tiered-KV A/B (round 22, serving/kv_cache.BlockStore): a churn
    workload — ``n_families`` prefix families (each a long shared head
    plus short unique suffixes) revisited across ``waves`` admission
    waves, with the registered working set sized to ~3x the device pool
    so every family is LRU-reclaimed between visits.  The multi-tenant
    shape where single-tier prefix caching stops working: the device-only
    arm forgets each family before its next wave and re-prefills the
    whole head; the tiered arm demotes evicted chains to host RAM and
    restores them at admission through the ``kv_transfer`` scatter.

    Reported:

    * ``serving_prefix_hit_rate_device_only`` vs ``_tiered`` (and the
      host-tier share) — read off each engine's own reuse/prompt token
      counters; the acceptance bar is tiered >= 1.5x device-only.
    * ``serving_tier_restore_p50_ms`` — admission-side wall time of one
      chain restore (fetch + CRC validate + device scatter), p50 over
      every restore in the run, vs ``serving_tier_reprefill_ms_est`` —
      what the replaced suffix prefill cost, estimated from the arm
      runtime delta per restore plus the restore itself.  On the CPU
      host both are smoke numbers; on chip the skipped prefill FLOPs
      dominate and the restore is a DMA.
    """
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Request, ServingEngine

    small = os.environ.get("BENCH_SERVING_SMALL") == "1"
    if small:
        n_families, batch, lmax, kvb = min(n_families, 12), 2, 512, 64
        cfg = LlamaConfig(
            vocab_size=2048, hidden_size=256, intermediate_size=688,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=2, max_position_embeddings=lmax,
            dtype="float32",
        )
        o_lo, o_hi = 16, 33
    else:
        lmax, kvb = 2048, 256
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=lmax,
            dtype="bfloat16",
        )
        o_lo, o_hi = 32, 65
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(22)
    # pool: 2 full-length requests; heads: 4 blocks each, so the
    # registered working set is n_families * 4 blocks ~= 3x the pool
    pool = 2 * lmax
    head_len = 4 * kvb
    heads = [rng.integers(0, cfg.vocab_size, head_len)
             for _ in range(n_families)]
    prompts, olens = [], []
    for _ in range(waves):
        for h in heads:
            sfx = rng.integers(0, cfg.vocab_size,
                               int(rng.integers(kvb // 4, kvb // 2)))
            prompts.append(np.concatenate([h, sfx]))
            olens.append(int(rng.integers(o_lo, o_hi)))
    total_new = int(sum(olens))

    def mk(tier_bytes=None):
        return ServingEngine(
            model, batch_size=batch, max_len=lmax, sync_every=4,
            decode_chunk=kvb, prefill_chunk=kvb, kv_block=kvb,
            max_live_tokens=pool, host_tier_bytes=tier_bytes,
            prompt_buckets=[lmax // 8, lmax // 4, lmax // 2,
                            3 * lmax // 4],
            instrument=False, recorder=False)

    def run(eng):
        for p, o in zip(prompts, olens):
            eng.submit(Request(p, o))
        t0 = time.perf_counter()
        eng.run()
        return time.perf_counter() - t0, eng.stats(), eng

    run(mk())                              # warm the compiled programs
    dt_dev, s_dev, _ = run(mk())
    run(mk(tier_bytes=1 << 30))            # warm incl. restore scatter
    dt_tier, s_tier, eng = run(mk(tier_bytes=1 << 30))

    hit_dev = s_dev["prefix_reuse_tokens"] / s_dev["prompt_tokens"]
    hit_tier = s_tier["prefix_reuse_tokens"] / s_tier["prompt_tokens"]
    hit_host = s_tier["host_reuse_tokens"] / s_tier["prompt_tokens"]
    restores = sorted(eng._restore_s)
    n_restores = len(restores)
    p50 = restores[n_restores // 2] * 1e3 if n_restores else None
    reprefill_est = (None if not n_restores else
                     max(0.0, dt_dev - dt_tier) * 1e3 / n_restores
                     + (p50 or 0.0))
    host = eng.kv_manager.host_tier
    return {
        "serving_tiered_kv_block": kvb,
        "serving_tiered_pool_tokens": pool,
        "serving_tiered_working_set_tokens": n_families * head_len,
        "serving_prefix_hit_rate_device_only": round(hit_dev, 3),
        "serving_prefix_hit_rate_tiered": round(hit_tier, 3),
        "serving_prefix_hit_rate_host": round(hit_host, 3),
        "serving_tier_hit_rate_ratio": (round(hit_tier / hit_dev, 2)
                                        if hit_dev > 0 else None),
        "serving_tiered_speedup": round(dt_dev / dt_tier, 2),
        "serving_tiered_tok_per_sec": round(total_new / dt_tier, 1),
        "serving_tier_restores": n_restores,
        "serving_tier_restore_p50_ms": (round(p50, 2)
                                        if p50 is not None else None),
        "serving_tier_reprefill_ms_est": (round(reprefill_est, 2)
                                          if reprefill_est is not None
                                          else None),
        "serving_tier_demoted_blocks": host.stats["demoted"],
        "serving_tier_restored_blocks": host.stats["restored"],
    }


def bench_serving_router(n_requests=64, n_replicas=2, batch=8):
    """Fleet router A/B (round 17, serving/router.Router): prefix-aware
    vs round-robin placement over ``n_replicas`` paged replicas on a
    multi-tenant workload — ``n_fam`` distinct prefix families (each a
    long shared system prompt plus short unique suffixes), arrivals
    interleaved across families the way real tenant traffic mixes.

    The fleet hit rate is a PLACEMENT property: a family only reuses its
    head's KV where it consistently lands.  Prefix-aware routing pins
    each family to one replica (first request by least-backlog, the rest
    via the router's radix mirror + engine probe), so only one head
    prefill per family fleet-wide; round-robin splits every family
    across all replicas and pays the head prefill ``n_replicas`` times
    — ``serving_router_hit_rate_prefix`` must clear 0.74 while the
    round-robin baseline sits below it, and the duplicated prefill work
    shows up as ``serving_router_speedup`` (decode work is identical by
    construction, so CPU-host speedups are modest; on chip the skipped
    head prefills are whole attention ramps).

    ``serving_preempt_recompute_ratio`` measures the suffix-cost
    preemption claim on one replica: park a low-priority decode under a
    high-priority arrival, then read resumed-suffix over resumed-total
    tokens off the engine's own counters — well under 1.0 means a
    preemption round-trip re-prefills only what the radix chain could
    not keep.
    """
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Replica, Request, Router, ServingEngine

    small = os.environ.get("BENCH_SERVING_SMALL") == "1"
    if small:
        n_requests, batch, lmax, kvb = min(n_requests, 32), 4, 512, 64
        cfg = LlamaConfig(
            vocab_size=2048, hidden_size=256, intermediate_size=688,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=2, max_position_embeddings=lmax,
            dtype="float32",
        )
        o_lo, o_hi = 16, 33
    else:
        lmax, kvb = 2048, 256
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=lmax,
            dtype="bfloat16",
        )
        o_lo, o_hi = 64, 129
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(17)
    n_fam = 4
    heads = [rng.integers(0, cfg.vocab_size, lmax // 2)
             for _ in range(n_fam)]
    sfx_lens = rng.integers(kvb // 4, kvb // 2 + 1, n_requests)
    prompts = [np.concatenate([heads[k % n_fam],
                               rng.integers(0, cfg.vocab_size, int(s))])
               for k, s in enumerate(sfx_lens)]
    olens = rng.integers(o_lo, o_hi, n_requests)
    total_new = int(olens.sum())
    # shuffled arrivals: tenant traffic interleaves, it doesn't arrive
    # family-sorted (a sorted order would hand round-robin accidental
    # family/replica alignment)
    order = rng.permutation(n_requests)
    geom = dict(batch_size=batch, max_len=lmax, sync_every=4,
                decode_chunk=kvb, prefill_chunk=kvb,
                prompt_buckets=[lmax // 8, lmax // 4, lmax // 2,
                                3 * lmax // 4],
                kv_block=kvb, max_live_tokens=batch * lmax,
                instrument=False, recorder=False)

    def mk_router(policy):
        return Router([Replica(ServingEngine(model, **geom),
                               name=f"rep{i}") for i in range(n_replicas)],
                      policy=policy)

    def run(router):
        # prime each tenant's head wherever the policy places it (ongoing
        # tenants, not cold start: the steady state placement is paid for)
        for f in range(n_fam):
            router.submit(Request(prompts[f], int(olens[f])))
        router.run()
        # the measured burst: every request, shuffled arrival order
        for k in order:
            router.submit(Request(prompts[k], int(olens[k])))
        t0 = time.perf_counter()
        router.run()
        dt = time.perf_counter() - t0
        hit = router.hit_rate()
        router.close()
        return dt, hit

    run(mk_router("prefix"))            # warm the compiled programs
    dt_prefix, hit_prefix = run(mk_router("prefix"))
    dt_rr, hit_rr = run(mk_router("round_robin"))

    # preemption cost on one replica: two low-priority decodes occupy
    # both slots, a high-priority arrival preempts one, the victim
    # resumes off its surviving radix chain
    eng = ServingEngine(model, **{**geom, "batch_size": 2})
    lows = [Request(p, 40) for p in prompts[:2]]
    for r in lows:
        eng.submit(r)
    for _ in range(6):
        eng.step()
    eng.submit(Request(prompts[2], 8, priority=5))
    eng.run()
    s = eng.stats()
    eng.close()

    return {
        "serving_router_replicas": n_replicas,
        "serving_router_families": n_fam,
        "serving_router_speedup": round(dt_rr / dt_prefix, 2),
        "serving_router_tok_per_sec": round(total_new / dt_prefix, 1),
        "serving_router_hit_rate_prefix": round(hit_prefix, 3),
        "serving_router_hit_rate_round_robin": round(hit_rr, 3),
        "serving_preempted": int(s["preempted"]),
        "serving_preempt_recompute_ratio": round(
            s["preempt_resume_suffix_tokens"]
            / max(1, s["preempt_resume_total_tokens"]), 3),
    }


def bench_serving_disagg(n_requests=32, batch=8):
    """Disaggregated prefill/decode A/B (round 18, serving/disagg.py):
    one colocated paged engine vs a 1-prefill + 1-decode split
    (DisaggCoordinator over InProcessTransport) on the same mixed
    long-prompt workload, decode geometry identical.

    The headline is the admission-interference tax on the loop that owns
    the decodes: per-token step latency — time spent inside the
    token-emitting engine's own ``step()`` calls per token drained —
    sampled while ANY request in the system is between submit and first
    token (an admission/prefill window).  For the colocated engine that
    loop dispatches prefill chunks and decodes together, so admission
    windows inflate its per-token cost; for the split, the decode
    worker's dispatch loop never sees a prefill chunk (migrations land
    in the coordinator pump, between steps), so
    ``serving_disagg_adm_tpot_p95_ms`` must land BELOW
    ``serving_colocated_adm_tpot_p95_ms``.  Step time, not wall-clock
    arrival gaps, because in-process both workers share one host thread
    — wall-clock would charge the prefill worker's chunks to decode
    tokens, an artifact a two-host deployment doesn't have.

    The cost side is the migration itself: ``serving_kv_transfer_p50_ms``
    (block-chain export -> transport -> import, off the coordinator's own
    histogram) — and since the first token is emitted BEFORE the
    transfer is paid (it rides the handoff), the TTFT gate is
    ``serving_disagg_ttft_p95_ms`` showing no regression over colocated
    beyond noise + transfer cost."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import MetricsRegistry
    from paddle_tpu.serving import (DecodeWorker, DisaggCoordinator,
                                    PrefillWorker, Request, ServingEngine)

    small = os.environ.get("BENCH_SERVING_SMALL") == "1"
    if small:
        n_requests, batch, lmax, kvb = min(n_requests, 24), 4, 512, 64
        cfg = LlamaConfig(
            vocab_size=2048, hidden_size=256, intermediate_size=688,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=2, max_position_embeddings=lmax,
            dtype="float32",
        )
        o_lo, o_hi = 16, 33
    else:
        lmax, kvb = 2048, 256
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=lmax,
            dtype="bfloat16",
        )
        o_lo, o_hi = 64, 129
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(23)
    # long-prompt-heavy mix: prompts at 25-50% of max_len keep chunked
    # prefills in flight throughout the run, so admission windows overlap
    # most of the decode work — the interference-visible regime
    p_lens = rng.integers(lmax // 4, lmax // 2 + 1, n_requests)
    prompts = [rng.integers(0, cfg.vocab_size, int(p)) for p in p_lens]
    olens = rng.integers(o_lo, o_hi, n_requests)
    total_new = int(olens.sum())
    geom = dict(batch_size=batch, max_len=lmax, sync_every=4,
                decode_chunk=kvb, prefill_chunk=kvb,
                prompt_buckets=[lmax // 4, lmax // 2],
                kv_block=kvb, max_live_tokens=batch * lmax,
                instrument=False, recorder=False)

    def drive(system, decode_engine):
        events = []                       # (t_emit, n_tokens)

        def cb(r, toks):
            events.append((time.perf_counter(), len(toks)))
        steps = []                        # decode-loop step (t0, t1)
        inner = decode_engine.step

        def timed_step():
            s0 = time.perf_counter()
            out = inner()
            steps.append((s0, time.perf_counter()))
            return out
        decode_engine.step = timed_step
        reqs = [Request(p, int(o), stream_cb=cb)
                for p, o in zip(prompts, olens)]
        for q in reqs:
            system.submit(q)
        t0 = time.perf_counter()
        system.run()
        dt = time.perf_counter() - t0
        system.close()
        # admission windows: submit -> first token, any request
        windows = [(q.t_submit, q.t_first) for q in reqs
                   if q.t_first is not None]
        # Per-token step latency: sync_every batches drains, so charge
        # the decode-loop step time ACCUMULATED since the last drain to
        # the tokens that drain releases; a sample is admission-active
        # when its drain lands inside some request's submit->first
        # window (the only time colocated steps carry prefill chunks).
        samples, acc, i = [], 0.0, 0
        for s0, s1 in steps:
            acc += s1 - s0
            toks = in_window = 0
            while i < len(events) and events[i][0] < s0:
                i += 1          # emitted outside the decode loop
                                # (disagg first tokens ride the handoff)
            while i < len(events) and events[i][0] <= s1:
                toks += events[i][1]
                if any(w0 <= events[i][0] <= w1 for w0, w1 in windows):
                    in_window += events[i][1]
                i += 1
            if toks:
                if in_window:
                    samples.extend([acc / toks] * in_window)
                acc = 0.0
        ttfts = [q.t_first - q.t_submit for q in reqs
                 if q.t_first is not None]
        return dt, samples, ttfts

    def colocated():
        eng = ServingEngine(model, **geom)
        return drive(eng, eng)

    reg = MetricsRegistry()

    def disagg(measured):
        pf = PrefillWorker(model, **geom)
        dec = DecodeWorker(model, **geom)
        return drive(
            DisaggCoordinator(pf, dec,
                              registry=reg if measured else None,
                              instrument=measured),
            dec.engine)

    colocated()                      # warm the compiled programs
    dt_co, adm_co, ttft_co = colocated()
    disagg(False)
    dt_dg, adm_dg, ttft_dg = disagg(True)

    xfer = reg.get("serving_kv_transfer_seconds").labels(
        coordinator="disagg0")
    migrations = int(xfer.count)
    return {
        "serving_disagg_requests": n_requests,
        "serving_disagg_migrations": migrations,
        "serving_colocated_adm_tpot_p95_ms": round(
            float(np.percentile(adm_co, 95)) * 1e3, 2) if adm_co else None,
        "serving_disagg_adm_tpot_p95_ms": round(
            float(np.percentile(adm_dg, 95)) * 1e3, 2) if adm_dg else None,
        "serving_kv_transfer_p50_ms": round(
            xfer.percentile(50) * 1e3, 2) if xfer.count else None,
        "serving_colocated_ttft_p95_ms": round(
            float(np.percentile(ttft_co, 95)) * 1e3, 1),
        "serving_disagg_ttft_p95_ms": round(
            float(np.percentile(ttft_dg, 95)) * 1e3, 1),
        "serving_disagg_tok_per_sec": round(total_new / dt_dg, 1),
        "serving_colocated_tok_per_sec": round(total_new / dt_co, 1),
    }


def bench_longseq(seqs=(16384, 32768), iters=3):
    """Long-context flash attention (VERDICT r4 next-round #7): causal
    fwd+bwd MFU of the streamed-KV Pallas kernels at 16k/32k tokens on one
    chip (GQA 16h/4kv, d=128, bf16 — the flagship head geometry).  MFU here
    is attention-matmul FLOPs (causal half, bwd counted 2.5x fwd) over
    wall-clock; the blockwise jnp fallback at 16k is recorded alongside as
    the non-Pallas baseline."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.flash_attention import (blockwise_attention,
                                                flash_attention_blhd)

    B, H, HKV, D = 1, 16, 4, 128
    out = {}
    peak = _peak_tflops()

    def measure(fn, L, backward=True):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, L, H, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, L, HKV, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, L, HKV, D), jnp.bfloat16)
        if backward:
            g = jax.grad(
                lambda a, b, c: fn(a, b, c).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))

            @jax.jit
            def chain(q, k, v):
                def body(i, c):
                    qq, kk, vv = c
                    dq, dk, dv = g(qq, kk, vv)
                    e = 1e-6
                    return ((qq + dq * e).astype(q.dtype),
                            (kk + dk * e).astype(q.dtype),
                            (vv + dv * e).astype(q.dtype))
                o = jax.lax.fori_loop(0, iters, body, (q, k, v))
                return o[0].sum() + o[1].sum() + o[2].sum()
        else:
            @jax.jit
            def chain(q, k, v):
                def body(i, qq):
                    return fn(qq, k, v).astype(q.dtype)
                return jax.lax.fori_loop(0, iters, body, q).sum()

        np.asarray(chain(q, k, v))
        t0 = time.perf_counter()
        np.asarray(chain(q, k, v))
        dt = (time.perf_counter() - t0) / iters
        # causal fwd matmul FLOPs; fwd+bwd counted as fwd + 2.5x fwd
        flops = 2 * B * H * L * L * D * (3.5 if backward else 1.0)
        return flops / dt / 1e12 / peak

    for L in seqs:
        out[f"flash_{L//1024}k_attn_mfu"] = round(measure(
            lambda a, b, c: flash_attention_blhd(a, b, c, causal=True), L), 4)
    # the jnp fallback is FORWARD-only at 16k: its backward is plain
    # autodiff through the scan, whose saved residuals exceed HBM at this
    # length — exactly why the Pallas kernels carry a custom backward
    out["blockwise_16k_fwd_attn_mfu"] = round(measure(
        lambda a, b, c: blockwise_attention(a, b, c, causal=True), 16384,
        backward=False), 4)
    return out


def bench_llama_long(iters=3, batch=1, seq=16384):
    """Model-level long-context training (SURVEY §5.7, the exceed-the-
    reference axis): the SAME flagship llama config at a 16k sequence —
    fused-RoPE + streamed-KV flash kernels end-to-end, full remat.  The
    attention share of the step grows quadratically, so blended MFU sits
    between the 2k train row and the 16k attention-kernel row."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.static.functionalize import build_train_step

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=4,
        max_position_embeddings=seq, dtype="bfloat16", recompute=True,
        loss_chunk_size=8192, recompute_layers=0)
    # rl0 (no remat): at B1 the HBM freed by batch=1 buys back every
    # recompute FLOP — swept rl16/12/8/4/0 = 1846/1719/1615/1520/1437 ms
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01, moment_dtype="int8")
    step = build_train_step(model, None, opt)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)),
                           dtype="int64")
    labels = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)),
                              dtype="int64")
    step(ids, labels).numpy()
    step(ids, labels).numpy()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(ids, labels)
    loss.numpy()
    dt = (time.perf_counter() - t0) / iters
    tok = batch * seq / dt
    fpt = 6 * n_params + 6 * cfg.num_hidden_layers * cfg.hidden_size * seq
    return {"llama_16k_train_mfu": round(fpt * tok / 1e12 / _peak_tflops(), 4),
            "llama_16k_tokens_per_sec": round(tok, 1)}


def bench_bert(iters=10, batch=64, seq=512):
    """BERT-base MLM pretraining samples/sec (BASELINE.md ERNIE/BERT north
    star; reference: PaddleNLP pretraining configs on Fleet DP)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertConfig, BertForMaskedLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.static.functionalize import build_train_step

    cfg = BertConfig(hidden_dropout_prob=0.0, dtype="bfloat16",
                     max_position_embeddings=seq)
    model = BertForMaskedLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())

    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01)

    # labels flow through the model's own masked-LM loss
    class _Net(paddle.nn.Layer):
        def __init__(self, m):
            super().__init__()
            self.m = m

        def forward(self, ids, labels):
            loss, _ = self.m(ids, labels=labels, return_logits=False)
            return loss

    step = build_train_step(_Net(model), None, opt)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)), dtype="int64")
    labels_np = rng.integers(0, cfg.vocab_size, (batch, seq))
    labels_np[rng.random((batch, seq)) > 0.15] = -100  # 15% masked positions
    labels = paddle.to_tensor(labels_np, dtype="int64")
    step(ids, labels).numpy()
    step(ids, labels).numpy()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(ids, labels)
    loss.numpy()
    dt = (time.perf_counter() - t0) / iters
    flops_per_token = 6 * n_params + 12 * cfg.num_hidden_layers \
        * cfg.hidden_size * seq  # full (bidirectional) attention
    mfu = flops_per_token * batch * seq / dt / 1e12 / _peak_tflops()
    return {"bert_base_samples_per_sec": round(batch / dt, 1),
            "bert_base_mfu": round(mfu, 4),
            "bert_step_ms": round(dt * 1000, 1)}


def bench_moe(iters=10, batch_tokens=16384, d_model=2048, n_experts=8):
    """MoE (expert-parallel layer) training step: tokens/sec through a top-2
    gshard-gated 8-expert FFN block (BASELINE.md DeepSeek-MoE stretch row;
    single chip exercises the dense dispatch/combine path, the ep dryrun
    covers the all-to-all)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.static.functionalize import build_train_step

    d_hidden = 4 * d_model

    class Expert(nn.Layer):
        def __init__(self):
            super().__init__()
            self.up = nn.Linear(d_model, d_hidden)
            self.down = nn.Linear(d_hidden, d_model)

        def forward(self, x):
            return self.down(paddle.nn.functional.gelu(self.up(x)))

    class Block(nn.Layer):
        def __init__(self):
            super().__init__()
            # gather = GShard capacity dispatch (r5): experts process only
            # their routed tokens — 4x fewer expert FLOPs than the dense
            # all-tokens formulation at top-2-of-8 (parity-tested)
            self.moe = MoELayer(d_model, [Expert() for _ in range(n_experts)],
                                gate={"type": "gshard", "top_k": 2},
                                dispatch="gather")

        def forward(self, x):
            return self.moe(x)

    model = Block()
    model.to(dtype="bfloat16")
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                moment_dtype="int8")  # the fused q8 kernel, as bench_llama
    step = build_train_step(model, paddle.nn.MSELoss(), opt)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(
        rng.standard_normal((batch_tokens, d_model)).astype(np.float32)
    ).astype("bfloat16")
    y = paddle.to_tensor(
        rng.standard_normal((batch_tokens, d_model)).astype(np.float32)
    ).astype("bfloat16")
    step(x, y).numpy()
    step(x, y).numpy()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(x, y)
    loss.numpy()
    dt = (time.perf_counter() - t0) / iters
    return {"moe_tokens_per_sec": round(batch_tokens / dt, 1),
            "moe_step_ms": round(dt * 1000, 1)}


def bench_eager(iters=200):
    """Eager (dygraph) dispatch throughput through the per-op jit cache,
    WITH the same model's fused compiled step next to it — the
    eager-vs-compiled gap quantified (VERDICT r3 weak #7)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.static.functionalize import build_train_step

    def make():
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(64, 64), nn.GELU(), nn.Linear(64, 64))
        opt = paddle.optimizer.SGD(learning_rate=1e-3,
                                   parameters=net.parameters())
        return net, opt

    x = paddle.to_tensor(np.random.randn(32, 64).astype("float32"))

    net, opt = make()

    def one():
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for _ in range(20):
        one()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = one()
    loss.numpy()
    dt = (time.perf_counter() - t0) / iters

    # identical model through the fused TrainStep (one XLA program/step)
    net2, opt2 = make()
    y = paddle.to_tensor(np.zeros((32, 64), np.float32))
    step = build_train_step(net2, nn.MSELoss(), opt2)
    step(x, y).numpy()
    step(x, y).numpy()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(x, y)
    loss.numpy()
    dtc = (time.perf_counter() - t0) / iters
    # label the platform: the absolute eager rate is dominated by host
    # dispatch — the eager_vs_compiled ratio is the portable number
    # (VERDICT r4 weak #5)
    import jax

    return {"eager_train_steps_per_sec": round(1.0 / dt, 1),
            "eager_platform": jax.devices()[0].platform,
            "compiled_train_steps_per_sec": round(1.0 / dtc, 1),
            "eager_vs_compiled": round(dt / dtc, 1)}


def bench_collectives():
    """fleet.collective_perf allreduce bandwidth (single-chip: measures the
    collective dispatch path; multi-chip ICI numbers need a pod)."""
    from paddle_tpu.distributed import fleet

    res = fleet.collective_perf("allreduce", round=20)
    best = max(res.values()) if res else 0.0
    return {"allreduce_gbps": round(float(best), 2)}


def bench_serving_fleet(n_requests=24, batch=4):
    """Multi-process disaggregated fleet (round 17, serving/launch.py):
    a config-launched 2-process 1P+1D deployment over a real UDS
    ``SocketTransport``, vs the colocated single-process engine on the
    same workload and geometry.

    What crossing a process boundary costs, measured where it is paid:

    * ``serving_fleet_kv_transfer_p50_ms`` — block-chain handoff over
      the wire (framed send -> reassembled recv), off the DECODE
      worker's own histogram (it owns the t_begin->adopt clock);
    * ``serving_fleet_overlap_stall_p50_ms`` — how long an arrived
      chain waited while the decode step loop had a slot free: ~0 means
      the background streamer really does overlap decode steps, the
      PTL017 seam doing its job across processes;
    * ``serving_fleet_adm_tpot_p95_ms`` — per-token inter-arrival
      latency at the PARENT for tokens landing while any request is
      between submit and first token.  The decode engine's own
      ``tpot_admission`` histogram is structurally empty out here —
      adoption is a block-table splice, never a prefill chunk, so the
      decode loop has no admission windows at all (that IS the
      disaggregation win); what is left to measure is whether the
      parent-visible stream stutters during admission, wire and all;
    * ``serving_fleet_ttft_p95_ms`` — first token rides the control
      plane (emitted before the transfer is paid), so TTFT carries one
      socket round-trip, not one chain transfer.

    The fleet model is pinned to the ``tiny`` preset (the only spec the
    worker process bootstraps), so cross-arm comparisons are overhead
    ratios, not absolute throughput.  Fleet: not run on the chip — the
    workers start on ``FleetConfig``'s default ``platform="cpu"``.

    Process order: the worker processes are launched and measured FIRST
    and the colocated arm runs after the fleet is closed, so this
    function starts no child from a parent that has already initialised
    a jax backend (a parent that holds a chip starves a child that needs
    one); ``main()`` runs this row before every other for the same
    reason."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (FleetConfig, Request, ServingEngine,
                                    launch)

    if os.environ.get("BENCH_SERVING_SMALL") == "1":
        n_requests = min(n_requests, 12)
    geom = dict(batch_size=batch, max_len=128, decode_chunk=16,
                prefill_chunk=16, kv_block=16,
                max_live_tokens=batch * 128,
                instrument=False, recorder=False)
    rng = np.random.default_rng(29)
    p_lens = rng.integers(24, 64, n_requests)
    prompts = [rng.integers(1, 255, int(p)).astype(np.int32)
               for p in p_lens]
    olens = rng.integers(12, 25, n_requests)
    total_new = int(olens.sum())

    def colocated():
        import paddle_tpu as paddle
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(dtype="float32"))
        model.eval()
        eng = ServingEngine(model, **geom)
        reqs = [eng.submit(Request(p, int(o)))
                for p, o in zip(prompts, olens)]
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        eng.close()
        return dt, reqs

    cfg = FleetConfig(engine=geom, n_prefill=1, n_decode=1,
                      heartbeat_s=1.0, ready_timeout_s=300)
    with launch(cfg, instrument=False) as fleet:
        coord = fleet.coordinator
        # warm the worker programs off the clock
        warm = [coord.submit(Request(p, 4)) for p in prompts[:batch]]
        coord.run(stall_timeout=300)
        assert all(r.status == "done" for r in warm)

        events = []                       # (t_arrival, n_tokens)

        def cb(r, toks):
            events.append((time.perf_counter(), len(toks)))

        reqs = [coord.submit(Request(p, int(o), stream_cb=cb))
                for p, o in zip(prompts, olens)]
        t0 = time.perf_counter()
        coord.run(stall_timeout=300)
        dt_fl = time.perf_counter() - t0
        dstats = fleet.handles["decode0"].request(
            {"cmd": "stats"})["stats"]
        fleet.close()

    dt_co, _ = colocated()
    dt_co, _ = colocated()     # second run: programs warm

    ttfts = [r.t_first - r.t_submit for r in reqs
             if r.t_first is not None]
    windows = [(r.t_submit, r.t_first) for r in reqs
               if r.t_first is not None]
    adm_samples = []
    for (t_prev, _), (t_cur, n) in zip(events, events[1:]):
        if n and any(w0 <= t_cur <= w1 for w0, w1 in windows):
            adm_samples.extend([(t_cur - t_prev) / n] * n)
    adm = (float(np.percentile(adm_samples, 95))
           if adm_samples else None)
    return {
        "serving_fleet_requests": n_requests,
        "serving_fleet_ttft_p95_ms": round(
            float(np.percentile(ttfts, 95)) * 1e3, 1),
        "serving_fleet_adm_tpot_p95_ms": round(adm * 1e3, 2)
        if adm is not None else None,
        "serving_fleet_kv_transfer_p50_ms": round(
            dstats["kv_transfer_p50_s"] * 1e3, 2)
        if dstats.get("kv_transfer_p50_s") else None,
        "serving_fleet_overlap_stall_p50_ms": round(
            dstats["overlap_stall_p50_s"] * 1e3, 3)
        if dstats.get("overlap_stall_p50_s") is not None else None,
        "serving_fleet_tok_per_sec": round(total_new / dt_fl, 1),
        "serving_fleet_colocated_tok_per_sec": round(
            total_new / dt_co, 1),
    }


def main():
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # the serving TP rows want >= 4 devices; on a CPU host that means
    # virtual ones, and the setting only takes before a backend exists
    # (it is inert on a TPU host)
    jax.config.update("jax_num_cpu_devices", 8)

    only = os.environ.get("BENCH_ONLY")  # e.g. "bench_serving": one table
    # bench_serving_fleet first: it spawns worker processes, and must do
    # so before this process has initialised a backend
    fns = (bench_serving_fleet, bench_resnet50, bench_bert, bench_moe,
           bench_decode, bench_serving, bench_serving_paged,
           bench_serving_tiered, bench_serving_router,
           bench_serving_disagg, bench_longseq,
           bench_llama_long, bench_eager, bench_collectives)
    failed = []

    def run_phase(fn, *args):
        """One phase at the boundary that must keep running: a failure is
        reported in the record and fails the process at the end."""
        try:
            return fn(*args)
        except Exception as e:
            traceback.print_exc()
            failed.append(fn.__name__)
            return {f"{fn.__name__}_error": f"{type(e).__name__}: {e}"[:160]}

    if only:
        out = {}
        for fn in fns:
            if fn.__name__ == only:
                out.update(run_phase(fn))
        print(json.dumps({**out, "device": _device()}))
        if failed:
            sys.exit(1)
        return

    if os.environ.get("BENCH_PRIMARY_ONLY") == "1":
        fns = ()
    secondary = {}
    for fn in fns[:1]:                      # the fleet row, before any backend
        secondary.update(run_phase(fn))
    iters = int(os.environ.get("BENCH_ITERS", "10"))
    rec = run_phase(bench_llama, iters)
    mfu = rec.pop("mfu", None)
    for fn in fns[1:]:
        secondary.update(run_phase(fn))

    baseline_path = os.path.join(os.path.dirname(__file__), "bench_baseline.json")
    vs = None
    if mfu is not None and os.path.exists(baseline_path):
        with open(baseline_path) as f:
            base = json.load(f)
        if base.get("mfu"):
            vs = round(mfu / float(base["mfu"]), 3)

    print(json.dumps({
        "metric": "llama_1b_train_mfu",
        "value": round(mfu, 4) if mfu is not None else None,
        "unit": "mfu",
        "vs_baseline": vs,
        "device": _device(),
        **rec,
        **secondary,
    }))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
