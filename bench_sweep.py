"""Perf sweep on the real chip: measure MFU across memory/remat configs
enabled by chunked CE + low-precision moments.  Appends one JSON line per
variant to chiprun_out/bench_sweep.jsonl (order: safe -> risky so OOMs lose
nothing).  The round-3/4 record of this sweep was taken on an older stack
and removed at bring-up; nothing here has been re-measured since.

Run on the chip: chiprun --timeout 3600 -- python -u bench_sweep.py

Round 9 adds the decode chunk-size sweep behind ``python -u bench_sweep.py
decode_chunk``: times the compiled serving decode step (serving_decode_steps,
bench model, batch 8, Lmax=2048) across chunk sizes x two occupancy regimes
(low ~128-token contexts, high ~1800).  The winner at low occupancy that is
regression-free at high occupancy becomes ServingEngine's ``decode_chunk``
default — 256 on the v5e-class chip this grew up on: small enough that a
128-token batch reads 1/8th of the cache, large enough that the per-chunk
while_loop overhead stays under the noise floor at full occupancy.

Round 10 adds ``python -u bench_sweep.py prefill_chunk``: a prefill
chunk-size x budget sweep over a long-prompt serving run (end-to-end
time + TPOT-p95-during-admission per variant, monolithic baseline
included) — the source of ServingEngine's ``prefill_chunk=256`` /
``prefill_budget=2`` defaults.

Round 15 adds ``python -u bench_sweep.py kv_dtype``: the KV-storage
dtype axis (bf16 vs the int8 cache with f16 per-(position, head)
scales) over the same low/high-occupancy regimes — per-step time plus
the analytic KV bytes per context token each storage mode moves.

Round 16 adds ``python -u bench_sweep.py attn_impl``: the
attention-read implementation axis (reference ``lax.while_loop``
chunked read vs the fused Pallas gather+dequant+online-softmax kernel)
crossed with the KV-storage dtype over the same occupancy regimes.

Round 22 adds ``python -u bench_sweep.py host_tier_bytes``: the tiered
KV cache's host-RAM budget axis over the churn workload (working set
~3x the device pool) — hit rate and restore p50 per budget, 0 = the
device-only baseline; the budget where the curve saturates is the host
RAM the working set actually needs.
"""
from __future__ import annotations

import gc
import json
import os
import time

import numpy as np

VARIANTS = [
    # name, batch, chunk, moment_dtype, policy, recompute_layers, kv_heads
    ("r4_b16_kv4_rl9", 16, 8192, "int8", None, 9, 4),
    ("r4_b16_kv4_rl8", 16, 8192, "int8", None, 8, 4),
    ("r4_b16_kv4_rl7", 16, 8192, "int8", None, 7, 4),
]


def run_variant(name, batch, chunk, md, policy, rl, kv_heads=16, iters=10):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.static.functionalize import build_train_step

    seq = 2048
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16,
        num_key_value_heads=kv_heads,
        max_position_embeddings=seq, dtype="bfloat16", recompute=True,
        loss_chunk_size=chunk, recompute_policy=policy, recompute_layers=rl,
    )
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01, moment_dtype=md)
    step = build_train_step(model, None, opt)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, 32000, (batch, seq)), dtype="int64")
    labels = paddle.to_tensor(rng.integers(0, 32000, (batch, seq)), dtype="int64")

    t_c = time.perf_counter()
    step(ids, labels).numpy()
    compile_s = time.perf_counter() - t_c
    step(ids, labels).numpy()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(ids, labels)
    lv = float(np.asarray(loss.numpy()))
    dt = (time.perf_counter() - t0) / iters
    tok_s = batch * seq / dt
    flops_per_token = 6 * n_params + 6 * 16 * 2048 * seq
    tflops = flops_per_token * tok_s / 1e12
    mfu = tflops / 197.0
    return {"variant": name, "mfu": round(mfu, 4), "tokens_per_sec": round(tok_s, 1),
            "step_ms": round(dt * 1000, 1), "tflops": round(tflops, 1),
            "compile_s": round(compile_s, 1), "loss": round(lv, 3)}


DECODE_CHUNKS = [None, 512, 256, 128, 64]


def sweep_decode_chunk(iters=20, n_steps=8):
    """Chunk-size sweep for the length-adaptive decode read: per-step time
    of the compiled serving step at each chunk size, in a low-occupancy
    regime (mean live context ~128 in an Lmax=2048 cache — where chunking
    pays) and a high-occupancy one (~1800 — where it must not regress).
    ``None`` is the full [B, Lmax] masked read (the pre-round-9 path)."""
    import jax.numpy as jnp

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.llama_decode import (
        _decode_params_of, serving_decode_steps)
    from paddle_tpu.ops.decode_attention import init_kv_cache

    lmax, batch = 2048, 8
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=4,
        max_position_embeddings=lmax, dtype="bfloat16",
    )
    model = LlamaForCausalLM(cfg)
    model.eval()
    params, key = _decode_params_of(model, lmax)
    nkv = cfg.num_key_value_heads
    hd = cfg.hidden_size // cfg.num_attention_heads
    rng = np.random.default_rng(0)
    cur = jnp.asarray(rng.integers(0, cfg.vocab_size, batch),
                      dtype=jnp.int32)
    regimes = {
        "low_occ": jnp.asarray(rng.integers(96, 161, batch), jnp.int32),
        "high_occ": jnp.asarray(rng.integers(1664, 1985, batch), jnp.int32),
    }
    rows = []
    for regime, lengths in regimes.items():
        for chunk in DECODE_CHUNKS:
            # caches are donated by the step — rebuild per config, carry
            # the returned buffers through the timing loop (the fixed
            # `lengths` keep every iteration's reads/writes identical)
            caches = [init_kv_cache(batch, lmax, nkv, hd, cfg.dtype)
                      for _ in range(cfg.num_hidden_layers)]
            toks, caches = serving_decode_steps(
                params, key, cur, caches, lengths,
                n_steps=n_steps, chunk_size=chunk)
            np.asarray(toks)  # compile + settle
            t0 = time.perf_counter()
            for _ in range(iters):
                toks, caches = serving_decode_steps(
                    params, key, cur, caches, lengths,
                    n_steps=n_steps, chunk_size=chunk)
            np.asarray(toks)
            dt = (time.perf_counter() - t0) / (iters * n_steps)
            rows.append({"variant": f"decode_chunk_{regime}_"
                         f"{'full' if chunk is None else chunk}",
                         "step_ms": round(dt * 1e3, 3),
                         "tok_per_sec": round(batch / dt, 1)})
            del caches
            gc.collect()
    return rows


KV_DTYPES = ["bfloat16", "int8"]


def sweep_kv_dtype(iters=20, n_steps=8):
    """KV-storage-dtype sweep for the quantized decode path: per-step
    time of the compiled serving decode step at each ``kv_dtype``
    (bf16 baseline vs the int8 cache with per-(position, head) f16
    scales), across the same low/high-occupancy regimes as the
    decode-chunk sweep.  The int8 rows move (D+2)/(2D) of the bf16 KV
    bytes per context token — on the HBM-bound chip that headroom is the
    win; the in-loop dequant multiplies are the cost being measured."""
    import jax.numpy as jnp

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.llama_decode import (
        _decode_params_of, serving_decode_steps)
    from paddle_tpu.ops.decode_attention import init_kv_cache
    from paddle_tpu.serving.program_key import ProgramKey

    lmax, batch, chunk = 2048, 8, 256
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=4,
        max_position_embeddings=lmax, dtype="bfloat16",
    )
    model = LlamaForCausalLM(cfg)
    model.eval()
    params, key = _decode_params_of(model, lmax)
    nkv = cfg.num_key_value_heads
    hd = cfg.hidden_size // cfg.num_attention_heads
    rng = np.random.default_rng(0)
    cur = jnp.asarray(rng.integers(0, cfg.vocab_size, batch), jnp.int32)
    regimes = {
        "low_occ": jnp.asarray(rng.integers(96, 161, batch), jnp.int32),
        "high_occ": jnp.asarray(rng.integers(1664, 1985, batch), jnp.int32),
    }
    rows = []
    for regime, lengths in regimes.items():
        for kvd in KV_DTYPES:
            caches = [init_kv_cache(batch, lmax, nkv, hd, kvd)
                      for _ in range(cfg.num_hidden_layers)]
            kv_dtype = kvd if kvd == "int8" else None
            pk = ProgramKey(kv_dtype=kv_dtype)
            toks, _, caches = serving_decode_steps(
                params, key, cur, caches, lengths,
                n_steps=n_steps, chunk_size=chunk, program_key=pk)
            np.asarray(toks)  # compile + settle
            t0 = time.perf_counter()
            for _ in range(iters):
                toks, _, caches = serving_decode_steps(
                    params, key, cur, caches, lengths,
                    n_steps=n_steps, chunk_size=chunk, program_key=pk)
            np.asarray(toks)
            dt = (time.perf_counter() - t0) / (iters * n_steps)
            per_tok = 2 if kvd == "bfloat16" else 1  # data bytes/elt
            kv_b = cfg.num_hidden_layers * 2 * nkv * (
                hd * per_tok + (2 if kvd == "int8" else 0))
            rows.append({"variant": f"kv_dtype_{regime}_{kvd}",
                         "step_ms": round(dt * 1e3, 3),
                         "tok_per_sec": round(batch / dt, 1),
                         "kv_bytes_per_ctx_tok": kv_b})
            del caches
            gc.collect()
    return rows


ATTN_IMPLS = [None, "pallas"]


def sweep_attn_impl(iters=20, n_steps=8):
    """Attention-read implementation sweep for the fused Pallas kernel:
    per-step time of the compiled serving decode step at each
    ``attn_impl`` (reference ``lax.while_loop`` chunked read vs the
    fused gather+dequant+online-softmax kernel) crossed with the
    KV-storage dtype, across the same low/high-occupancy regimes as the
    decode-chunk sweep.  The fused x int8 cell is the headline: the
    kernel keeps each KV chunk in one VMEM residency, so the dequant
    multiplies that cost the reference path its in-loop bandwidth ride
    for free."""
    import jax.numpy as jnp

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.llama_decode import (
        _decode_params_of, serving_decode_steps)
    from paddle_tpu.ops.decode_attention import init_kv_cache
    from paddle_tpu.serving.program_key import ProgramKey

    lmax, batch, chunk = 2048, 8, 256
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=4,
        max_position_embeddings=lmax, dtype="bfloat16",
    )
    model = LlamaForCausalLM(cfg)
    model.eval()
    params, key = _decode_params_of(model, lmax)
    nkv = cfg.num_key_value_heads
    hd = cfg.hidden_size // cfg.num_attention_heads
    rng = np.random.default_rng(0)
    cur = jnp.asarray(rng.integers(0, cfg.vocab_size, batch), jnp.int32)
    regimes = {
        "low_occ": jnp.asarray(rng.integers(96, 161, batch), jnp.int32),
        "high_occ": jnp.asarray(rng.integers(1664, 1985, batch), jnp.int32),
    }
    rows = []
    for regime, lengths in regimes.items():
        for kvd in KV_DTYPES:
            for impl in ATTN_IMPLS:
                caches = [init_kv_cache(batch, lmax, nkv, hd, kvd)
                          for _ in range(cfg.num_hidden_layers)]
                kv_dtype = kvd if kvd == "int8" else None
                pk = ProgramKey(kv_dtype=kv_dtype, attn_impl=impl)
                toks, _, caches = serving_decode_steps(
                    params, key, cur, caches, lengths,
                    n_steps=n_steps, chunk_size=chunk, program_key=pk)
                np.asarray(toks)  # compile + settle
                t0 = time.perf_counter()
                for _ in range(iters):
                    toks, _, caches = serving_decode_steps(
                        params, key, cur, caches, lengths,
                        n_steps=n_steps, chunk_size=chunk, program_key=pk)
                np.asarray(toks)
                dt = (time.perf_counter() - t0) / (iters * n_steps)
                label = "pallas" if impl == "pallas" else "reference"
                rows.append({"variant": f"attn_impl_{regime}_{kvd}_{label}",
                             "step_ms": round(dt * 1e3, 3),
                             "tok_per_sec": round(batch / dt, 1)})
                del caches
                gc.collect()
    return rows


PREFILL_CHUNKS = [64, 128, 256, 512]
PREFILL_BUDGETS = [1, 2, 4]


def sweep_prefill_chunk(n_requests=24):
    """Chunk-size x budget sweep for budgeted chunked prefill: end-to-end
    time and TPOT-p95-during-admission of a long-prompt-heavy serving run
    (prompts 1024-1792 in an Lmax=2048 cache, outputs 64-128 — admissions
    keep landing while residents decode) at each (prefill_chunk,
    prefill_budget), against the monolithic per-bucket baseline
    (``prefill_chunk=None``).  Picks the engine defaults: the smallest
    interference number that doesn't cost end-to-end throughput."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import MetricsRegistry
    from paddle_tpu.serving import Request, ServingEngine

    lmax, batch = 2048, 8
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=4,
        max_position_embeddings=lmax, dtype="bfloat16",
    )
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    plens = rng.integers(1024, 1793, n_requests)
    olens = rng.integers(64, 129, n_requests)
    reqs = [(np.tile(rng.integers(0, cfg.vocab_size, 32),
                     p // 32 + 1)[:p], int(o)) for p, o in zip(plens, olens)]
    total_new = int(olens.sum())

    def run(pchunk, budget):
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=batch, max_len=lmax,
                            sync_every=4, registry=reg,
                            prefill_chunk=pchunk, prefill_budget=budget)
        for p, o in reqs:
            eng.submit(Request(p, o))
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        h = reg.get("serving_tpot_during_admission_seconds").labels(
            policy="continuous")
        p95 = round(h.percentile(95) * 1e3, 1) if h.count else None
        return dt, p95

    rows = []
    variants = [(None, 1)] + [(c, b) for c in PREFILL_CHUNKS
                              for b in PREFILL_BUDGETS]
    for pchunk, budget in variants:
        run(pchunk, budget)  # warm this configuration's programs
        dt, p95 = run(pchunk, budget)
        name = ("prefill_monolithic" if pchunk is None
                else f"prefill_chunk_{pchunk}_budget_{budget}")
        rows.append({"variant": name, "e2e_s": round(dt, 2),
                     "tok_per_sec": round(total_new / dt, 1),
                     "adm_tpot_p95_ms": p95})
        gc.collect()
    return rows


PREFILL_IMPLS = [None, "pallas"]


def sweep_prefill_impl(n_requests=24):
    """Prefill-implementation sweep for the fused Pallas chunked-prefill
    kernel: end-to-end time and TPOT-p95-during-admission of the same
    long-prompt-heavy paged serving run as the prefill-chunk sweep, at
    each ``prefill_impl`` (reference dense fold + scatter append vs the
    fused attention+append kernel) crossed with the KV-storage dtype.
    The fused x int8 cell is the headline: quantize-on-append happens
    inside the kernel, so the separate scatter pass disappears."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import MetricsRegistry
    from paddle_tpu.serving import Request, ServingEngine

    lmax, batch, pchunk = 2048, 8, 256
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=4,
        max_position_embeddings=lmax, dtype="bfloat16",
    )
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    plens = rng.integers(1024, 1793, n_requests)
    olens = rng.integers(64, 129, n_requests)
    reqs = [(np.tile(rng.integers(0, cfg.vocab_size, 32),
                     p // 32 + 1)[:p], int(o)) for p, o in zip(plens, olens)]
    total_new = int(olens.sum())

    def run(impl, kv_dtype):
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=batch, max_len=lmax,
                            sync_every=4, registry=reg,
                            kv_block=pchunk, prefill_chunk=pchunk,
                            prefill_budget=2, prefill_impl=impl,
                            kv_dtype=kv_dtype)
        for p, o in reqs:
            eng.submit(Request(p, o))
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        h = reg.get("serving_tpot_during_admission_seconds").labels(
            policy="continuous")
        p95 = round(h.percentile(95) * 1e3, 1) if h.count else None
        return dt, p95

    rows = []
    for kv_dtype in (None, "int8"):
        for impl in PREFILL_IMPLS:
            run(impl, kv_dtype)  # warm this configuration's programs
            dt, p95 = run(impl, kv_dtype)
            label = "pallas" if impl == "pallas" else "reference"
            kvd = kv_dtype or "bf16"
            rows.append({"variant": f"prefill_impl_{kvd}_{label}",
                         "e2e_s": round(dt, 2),
                         "tok_per_sec": round(total_new / dt, 1),
                         "adm_tpot_p95_ms": p95})
            gc.collect()
    return rows


SPEC_KS = [2, 4, 8]


def sweep_spec_k(n_requests=16):
    """Draft-depth axis for resident-draft-model speculation: fixed
    ``spec_k`` rungs vs the adaptive ladder (spec_k=8, k_min=1,
    accept-rate window 8), crossed with two drafters that bracket the
    acceptance range — ``self`` (the target drafting for itself,
    accept ~1.0: deep drafts pay off, adaptive should hold the top
    rung) and ``shrunk`` (a quarter-depth random-init draft, accept
    near chance: every drafted token is wasted work, adaptive should
    walk down to k_min).  The point of the axis: no fixed k wins both
    regimes, the ladder should track the better fixed rung in each.
    Off-chip the times are ratio-only (the draft forward runs at host
    speed); accept rates and the settled depth are real."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import MetricsRegistry
    from paddle_tpu.serving import Request, ServingEngine
    from paddle_tpu.serving.engine import SpecConfig

    lmax, batch = 2048, 8
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=4,
        max_position_embeddings=lmax, dtype="bfloat16",
    )
    model = LlamaForCausalLM(cfg)
    model.eval()
    dcfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=4, num_attention_heads=16, num_key_value_heads=4,
        max_position_embeddings=lmax, dtype="bfloat16",
    )
    shrunk = LlamaForCausalLM(dcfg)
    shrunk.eval()
    rng = np.random.default_rng(0)
    plens = rng.integers(64, 513, n_requests)
    olens = rng.integers(64, 129, n_requests)
    reqs = [(np.tile(rng.integers(0, cfg.vocab_size, 32),
                     p // 32 + 1)[:p], int(o)) for p, o in zip(plens, olens)]
    total_new = int(olens.sum())

    def run(drafter, spec):
        reg = MetricsRegistry()
        eng = ServingEngine(model, batch_size=batch, max_len=lmax,
                            mode="spec", sync_every=4, registry=reg,
                            spec_k=spec.spec_k, spec=spec,
                            kv_block=256, prefill_chunk=256,
                            max_live_tokens=2 * batch * lmax)
        for p, o in reqs:
            eng.submit(Request(p, o))
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        rate = reg.get("serving_spec_accept_rate").labels(
            policy="continuous", source="draft_model").value
        k_end = reg.get("serving_spec_draft_k").labels(
            policy="continuous").value
        return dt, rate, k_end

    rows = []
    for dname, drafter in (("self", model), ("shrunk", shrunk)):
        variants = [SpecConfig(source="draft_model", draft_model=drafter,
                               spec_k=k) for k in SPEC_KS]
        variants.append(SpecConfig(source="draft_model", draft_model=drafter,
                                   spec_k=8, k_min=1, adaptive_window=8))
        for spec in variants:
            run(dname, spec)  # warm this configuration's programs
            dt, rate, k_end = run(dname, spec)
            kname = ("adaptive" if spec.adaptive_window is not None
                     else f"k{spec.spec_k}")
            rows.append({"variant": f"spec_{dname}_{kname}",
                         "e2e_s": round(dt, 2),
                         "tok_per_sec": round(total_new / dt, 1),
                         "accept_rate": round(rate, 3),
                         "draft_k_end": int(k_end)})
            gc.collect()
    return rows


HOST_TIER_BYTES = [0, 1 << 26, 1 << 28, 1 << 30]


def sweep_host_tier_bytes(n_families=12, waves=3):
    """Host-tier byte-budget sweep for the tiered KV cache: the
    bench_serving_tiered churn workload (prefix families whose
    registered working set is ~3x the device pool, revisited across
    admission waves) at each ``host_tier_bytes`` budget, 0 = the
    device-only baseline.  End-to-end time, combined hit rate, and the
    restore p50 — the budget where the hit-rate curve saturates is how
    much host RAM the working set actually needs; past it the tier's own
    LRU stops evicting and extra budget buys nothing."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Request, ServingEngine

    lmax, kvb, batch = 2048, 256, 2
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=4,
        max_position_embeddings=lmax, dtype="bfloat16",
    )
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(22)
    pool, head_len = 2 * lmax, 4 * kvb
    heads = [rng.integers(0, cfg.vocab_size, head_len)
             for _ in range(n_families)]
    reqs = []
    for _ in range(waves):
        for h in heads:
            sfx = rng.integers(0, cfg.vocab_size,
                               int(rng.integers(kvb // 4, kvb // 2)))
            reqs.append((np.concatenate([h, sfx]),
                         int(rng.integers(32, 65))))
    total_new = sum(o for _, o in reqs)

    def run(tier_bytes):
        eng = ServingEngine(
            model, batch_size=batch, max_len=lmax, sync_every=4,
            decode_chunk=kvb, prefill_chunk=kvb, kv_block=kvb,
            max_live_tokens=pool,
            host_tier_bytes=tier_bytes or None,
            prompt_buckets=[lmax // 8, lmax // 4, lmax // 2,
                            3 * lmax // 4],
            instrument=False, recorder=False)
        for p, o in reqs:
            eng.submit(Request(p, o))
        t0 = time.perf_counter()
        eng.run()
        return time.perf_counter() - t0, eng

    rows = []
    for tb in HOST_TIER_BYTES:
        run(tb)  # warm this configuration's programs
        dt, eng = run(tb)
        s = eng.stats()
        restores = sorted(eng._restore_s)
        p50 = (round(restores[len(restores) // 2] * 1e3, 2)
               if restores else None)
        rows.append({
            "variant": ("tier_off" if not tb
                        else f"host_tier_{tb >> 20}mb"),
            "e2e_s": round(dt, 2),
            "tok_per_sec": round(total_new / dt, 1),
            "hit_rate": round(s["prefix_reuse_tokens"]
                              / max(1, s["prompt_tokens"]), 3),
            "host_hit_rate": round(s["host_reuse_tokens"]
                                   / max(1, s["prompt_tokens"]), 3),
            "restore_p50_ms": p50,
        })
        gc.collect()
    return rows


def main():
    import sys

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # chiprun_out/ is what comes back from a chip run (and git ignores it)
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "bench_sweep.jsonl")

    def record(rec):
        print(json.dumps(rec), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(rec) + "\n")

    sweeps = {
        "decode_chunk": sweep_decode_chunk,
        "prefill_chunk": sweep_prefill_chunk,
        "kv_dtype": sweep_kv_dtype,
        "attn_impl": sweep_attn_impl,
        "host_tier_bytes": sweep_host_tier_bytes,
        "spec_k": sweep_spec_k,
        "prefill_impl": sweep_prefill_impl,
    }
    if len(sys.argv) > 1 and sys.argv[1] in sweeps:
        for rec in sweeps[sys.argv[1]]():
            record(rec)
        return
    for v in VARIANTS:
        print(f"=== {v[0]} ===", flush=True)
        try:
            rec = run_variant(*v)
        except Exception as e:  # OOM etc: record and continue
            rec = {"variant": v[0], "error": f"{type(e).__name__}: {e}"[:400]}
        record(rec)
        gc.collect()


if __name__ == "__main__":
    main()
