#!/usr/bin/env python3
"""The quickest proof that paddle_tpu still starts on the chip.

    python3 chip_smoke.py              # one TPU chip: serve, then train
    python3 chip_smoke.py --multichip  # four chips: tensor-parallel serve only

Drives the two main paths once through their public entry points, at the
full width (and depth) of a 0.95 B-parameter Llama, with random weights
from a fixed seed (the benchmark's cells measure other, published widths:
``benchmark/configs/``):

* **serve** — ``LlamaForCausalLM`` (vocab 32000, hidden 2048, 16 layers,
  16 heads / 4 kv heads, bf16, max_len 2048) behind
  ``paddle_tpu.serving.ServingEngine`` via ``submit``/``run``: once on the
  engine's default options, once with a paged int8 pool and both fused
  Pallas kernels (``attn_impl="pallas"``, ``prefill_impl="pallas"``).
* **train** — the same model (0.95 B parameters, batch
  16 x seq 2048, int8/bf16 Adam moments, ``recompute_layers=7``, chunked
  CE) through ``static.functionalize.build_train_step``, three steps.
* **multichip** (``--multichip`` only) — the serve path on
  ``ServingEngine(model, mesh=Mesh(devices[:4], ("mp",)))`` against the
  single-device engine in the same process.

What it does NOT cover: the multi-process fleet (``serving/launch.py`` —
not run on the chip), speculative decoding, the host KV tier, the router.

Each phase checks its results by the repo's own means (see the phase
functions) and any failed check fails the script.  The last line of
standard output is one JSON object —
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
on success; everything else worth knowing is on earlier lines.  Without a
TPU the script exits non-zero: there is no CPU fallback.

One process owns a chip, so this parent never imports ``jax`` (nor
``paddle_tpu``, whose import does): it starts one child per phase in turn
(``--phase`` is that internal hand-off) and each child gets a fresh chip.
"""
import argparse
import gc
import json
import os
import subprocess
import sys
import threading
import time

BUDGET_S = 1150          # the whole script, compilation included (limit 1200)
#: a first-token mismatch against the reference counts only when the
#: reference scores the engine's token more than this below its own pick.
#: 0.0625 = 4 bf16 ulps at the observed logit magnitude (|logit| in [2, 4),
#: ulp 2^-6): two bf16 implementations of one random-init model tie inside
#: that band, and a real fault lands far outside it.
LOGIT_TOL = 0.0625
#: sharded vs single-device logits: row-parallel psums reassociate bf16
#: sums, so a few ulps at the same magnitude
TP_LOGIT_TOL = 0.125

FULL = dict(
    model=dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
               num_hidden_layers=16, num_attention_heads=16,
               num_key_value_heads=4, dtype="bfloat16"),
    serve=dict(batch_size=8, max_len=2048),
    fused=dict(kv_block=128, attn_impl="pallas", prefill_impl="pallas",
               kv_dtype="int8"),
    prompts=(200, 333, 517, 260, 400, 128), prompts2=(150, 290, 450),
    new_tokens=32, ref_len=640, probe=(4096, 400),
    train=dict(batch=16, seq=2048, loss_chunk_size=8192, recompute_layers=7),
)
# the same phases at a size the CPU interpreter finishes in seconds — the
# rehearsal tests/test_chip_smoke.py runs; never what the script itself runs
TOY = dict(
    model=dict(vocab_size=512, hidden_size=128, intermediate_size=256,
               num_hidden_layers=2, num_attention_heads=8,
               num_key_value_heads=4, dtype="float32"),
    serve=dict(batch_size=4, max_len=128),
    fused=dict(kv_block=16, prefill_chunk=16, attn_impl="pallas",
               prefill_impl="pallas", kv_dtype="int8"),
    prompts=(9, 21, 33, 16), prompts2=(12, 27),
    new_tokens=6, ref_len=48, probe=(64, 4),
    train=dict(batch=2, seq=128, loss_chunk_size=64, recompute_layers=1),
)


def say(phase, **kv):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class Checks:
    """Named pass/fail checks of one phase; every one is printed, and one
    failure fails the phase."""

    def __init__(self, phase):
        self.phase = phase
        self.failed = []

    def __call__(self, name, ok, detail=""):
        ok = bool(ok)
        say(self.phase, check=name, ok=ok, **({"detail": detail}
                                             if detail != "" else {}))
        if not ok:
            self.failed.append(name)
        return ok


# ------------------------------------------------------------ in the children
def compile_events(since=None):
    """JAX's compile work so far, read from the program's own start-up
    record (``paddle_tpu.observability.compilecache``): executables loaded
    or compiled, how many of them the persistent cache answered, and the
    seconds they took — or what was added after ``since`` (an earlier
    reading)."""
    from paddle_tpu.observability.compilecache import startup

    log = startup.entries()
    loads = [e["t_end"] - e["t_start"] for e in log if e["stage"] == "load"]
    now = dict(cache_hits=sum(e["stage"] == "cache_retrieval" for e in log),
               backend_compiles=len(loads), compile_s=round(sum(loads), 1))
    if since is not None:
        now = {k: round(v - since[k], 1) for k, v in now.items()}
    return now


def start(phase, chip, n_devices=1):
    """Common child prologue: the device (asserted to be a TPU when
    ``chip``) and the compile cache."""
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(phase, **device)
    if chip and dev.platform != "tpu":
        raise SystemExit(f"[{phase}] no TPU: jax.devices()[0].platform is "
                         f"{dev.platform!r} — this script has no CPU "
                         f"fallback")
    if len(jax.devices()) < n_devices:
        raise SystemExit(f"[{phase}] needs {n_devices} devices, jax sees "
                         f"{len(jax.devices())}")
    say(phase, compile_cache_dir=cache_dir)
    return device


def sync_probe(phase, size):
    """Does ``jax.block_until_ready`` block?  Dispatch a chain of matmuls
    (~0.3 s on a v5e at the full size), then time block_until_ready and a
    following host read-back of the same array: the one that waits for
    the device takes the time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, iters = size["probe"]

    @jax.jit
    def busy(x):
        return jax.lax.fori_loop(
            0, iters, lambda _, a: (a @ a) * jnp.bfloat16(1.0 / n), x)

    x = jnp.ones((n, n), jnp.bfloat16)
    np.asarray(busy(x)[0, 0])                       # compile + settle
    t0 = time.perf_counter()
    y = busy(x)
    t1 = time.perf_counter()
    jax.block_until_ready(y)
    t2 = time.perf_counter()
    np.asarray(y[0, 0])
    t3 = time.perf_counter()
    say(phase, sync_probe="dispatch/block_until_ready/readback_after",
        dispatch_s=round(t1 - t0, 4), block_until_ready_s=round(t2 - t1, 4),
        readback_after_s=round(t3 - t2, 4),
        block_until_ready_blocks=(t2 - t1) > 10 * (t3 - t2))


def build_model(size):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(max_position_embeddings=size["serve"]["max_len"],
                      **size["model"])
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


def make_prompts(size, key, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, size["model"]["vocab_size"], n).astype(np.int32)
            for n in size[key]]


def reference_logits(model, prompts, ref_len):
    """Next-token logits of each prompt from the MODEL'S OWN forward (the
    training-side ``LlamaForCausalLM.forward`` — not the serving decode
    path), one request at a time, right-padded to one compiled length (the
    pad is causally invisible to the prompt's last position)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.static.functionalize import build_eval_fn

    fwd = build_eval_fn(model)
    out = []
    for p in prompts:
        ids = np.zeros((1, ref_len), np.int64)
        ids[0, :len(p)] = p
        logits = fwd(paddle.to_tensor(ids, dtype="int64"))
        out.append(np.asarray(logits.data[0, len(p) - 1], np.float32))
    return out


def first_token_check(check, name, firsts, refs):
    """The engine's first tokens against the reference logits: a mismatch
    counts only when the reference scores the engine's token more than
    LOGIT_TOL below its own pick (random-init models tie)."""
    import numpy as np

    exact = ties = bad = 0
    for tok, ref in zip(firsts, refs):
        top = int(np.argmax(ref))
        if tok == top:
            exact += 1
        elif ref[top] - ref[tok] <= LOGIT_TOL:
            ties += 1
        else:
            bad += 1
    top2 = [float(np.diff(np.sort(r)[-2:])[0]) for r in refs]
    return check(name, bad == 0 and all(np.isfinite(r).all() for r in refs),
                 f"exact={exact} ties_within_{LOGIT_TOL}={ties} "
                 f"mismatch={bad} ref_top2_margins="
                 f"{[round(m, 3) for m in top2]} "
                 f"max_abs_logit={max(float(np.abs(r).max()) for r in refs):.2f}")


def run_wave(eng, prompts, new_tokens):
    from paddle_tpu.serving import Request

    reqs = [eng.submit(Request(p, new_tokens)) for p in prompts]
    t0 = time.perf_counter()
    eng.run()
    return reqs, time.perf_counter() - t0


def all_done(reqs, new_tokens):
    return all(r.status == "done" and len(r.output_ids) == new_tokens
               for r in reqs)


def engine_leaves(eng):
    """Every device array the engine holds: weights and KV."""
    import jax

    return [x for x in jax.tree.leaves((eng._params, eng._kv.caches))
            if isinstance(x, jax.Array)]


def compiled_texts(eng):
    """The compiled text of the engine's decode and prefill-chunk programs,
    lowered from the engine's own live operands — the very programs its
    waves ran, so the compile is a cache hit (nothing is donated or run)."""
    import jax.numpy as jnp

    from paddle_tpu.models.llama_decode import (serving_decode_steps,
                                                serving_prefill_chunk)

    params, caches = eng._params, eng._kv.caches
    tables = eng._tables() if eng._paged else None
    rows = jnp.zeros((eng._B,), jnp.int32)
    scalar = jnp.int32(0)
    if eng._tp is not None:
        t = (tables,) if eng._paged else ()
        decode = eng._tp.decode_steps.__wrapped__.lower(
            params, rows, caches, rows, *t)
        prefill = None
    else:
        decode = serving_decode_steps.__wrapped__.lower(
            params, eng._cfg, rows, caches, rows, n_steps=eng._sync,
            chunk_size=eng._chunk, block_tables=tables, program_key=eng._pk)
        prefill = serving_prefill_chunk.__wrapped__.lower(
            params, eng._cfg, jnp.zeros((1, eng._pchunk), jnp.int32), scalar,
            jnp.zeros((1,), jnp.int32), caches, scalar,
            hist=None, hist_len=None, with_hist=False,
            chunk_size=eng._chunk, block_tables=tables,
            program_key=eng._pk).compile().as_text()
    return decode.compile().as_text(), prefill


def serve_phase(size=FULL, chip=True):
    """Serve on one chip: default engine, then the fused paged-int8 engine.
    Returns (device, failed_checks)."""
    import jax

    from paddle_tpu.analysis import RetraceError, assert_no_retrace
    from paddle_tpu.core.native.build import build as native_build
    from paddle_tpu.ops import paged_attention_pallas as pap
    from paddle_tpu.serving import ServingEngine

    phase = "serve"
    device = start(phase, chip)
    check = Checks(phase)
    t0 = time.perf_counter()
    say(phase, native_libs_built=native_build(),
        native_build_s=round(time.perf_counter() - t0, 1))
    sync_probe(phase, size)

    t0 = time.perf_counter()
    model = build_model(size)
    n_params = sum(int(p.size) for p in model.parameters())
    say(phase, params_b=round(n_params / 1e9, 3),
        model_build_s=round(time.perf_counter() - t0, 1))
    new = size["new_tokens"]
    prompts = make_prompts(size, "prompts", seed=1)
    prompts2 = make_prompts(size, "prompts2", seed=2)
    t0 = time.perf_counter()
    refs = reference_logits(model, prompts, size["ref_len"])
    say(phase, reference_forward_s=round(time.perf_counter() - t0, 1))

    firsts = {}
    for label, opts in (("default", {}), ("fused", size["fused"])):
        tag = f"{phase}/{label}"
        before = compile_events()
        eng = ServingEngine(model, **size["serve"], **opts)
        reqs, cold_s = run_wave(eng, prompts, new)
        say(tag, wave="cold", requests=len(reqs), seconds=round(cold_s, 2),
            tokens=sum(len(r.output_ids) for r in reqs),
            **compile_events(since=before))
        check(f"{label}: every request done with {new} tokens",
              all_done(reqs, new), [r.status for r in reqs])
        on = {d for leaf in engine_leaves(eng) for d in leaf.devices()}
        check(f"{label}: parameters and KV live on the device",
              on == {jax.devices()[0]}, sorted(str(d) for d in on))
        firsts[label] = [r.output_ids[0] for r in reqs]
        first_token_check(
            check, f"{label}: first tokens agree with the model's own "
            f"forward (finite logits)", firsts[label], refs)
        # the second wave: new prompt lengths on the warm engine
        before, retraced = compile_events(), ""
        try:
            with assert_no_retrace():
                reqs2, warm_s = run_wave(eng, prompts2, new)
        except RetraceError as e:
            retraced = str(e)
        else:
            say(tag, wave="warm", requests=len(reqs2),
                seconds=round(warm_s, 2),
                tokens=sum(len(r.output_ids) for r in reqs2),
                **compile_events(since=before))
        check(f"{label}: warm wave done, nothing retraced",
              not retraced and all_done(reqs2, new), retraced)
        if label == "fused":
            decode, prefill = compiled_texts(eng)
            if chip:
                check("fused: tpu_custom_call in the compiled decode program",
                      "tpu_custom_call" in decode)
                check("fused: tpu_custom_call in the compiled prefill "
                      "program", "tpu_custom_call" in prefill)
            check("fused: no kernel fell back to the reference path",
                  not pap._warned, sorted(pap._warned))
        eng.close()
        del eng
        gc.collect()
    say(phase, first_tokens_default=firsts["default"],
        first_tokens_fused=firsts["fused"], **compile_events())
    return device, check.failed


def train_phase(size=FULL, chip=True):
    """Three fused train steps of the 0.95 B configuration on one
    repeated batch.  Returns (device, failed_checks)."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.static.functionalize import build_train_step

    phase = "train"
    device = start(phase, chip)
    check = Checks(phase)
    sync_probe(phase, size)
    tr = size["train"]
    paddle.seed(0)
    cfg = LlamaConfig(max_position_embeddings=tr["seq"], recompute=True,
                      loss_chunk_size=tr["loss_chunk_size"],
                      recompute_layers=tr["recompute_layers"],
                      **size["model"])
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(p.size) for p in model.parameters())
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01, moment_dtype="int8")
    step = build_train_step(model, None, opt)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (tr["batch"], tr["seq"])),
        dtype="int64")
    say(phase, params_b=round(n_params / 1e9, 3), batch=tr["batch"],
        seq=tr["seq"])

    losses, secs = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        loss = step(ids, ids)
        jax.block_until_ready(loss.data)
        losses.append(float(loss.numpy()))
        secs.append(round(time.perf_counter() - t0, 2))
    say(phase, losses=[round(l, 4) for l in losses], step_seconds=secs,
        tokens_per_step=tr["batch"] * tr["seq"], **compile_events())
    check("loss finite and falling",
          all(np.isfinite(losses)) and losses[2] < losses[0], losses)
    # the compiled text, lowered from the step's own live operands as
    # TrainStep.__call__ passes them: the program the steps ran, so the
    # compile is a cache hit (nothing is donated or run)
    t0 = time.perf_counter()
    text = step.lower(ids, ids).compile().as_text()
    say(phase, lower_and_compile_s=round(time.perf_counter() - t0, 1),
        **compile_events())
    if chip:
        calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
        for kernel, marker in (("flash-attention fwd", "_flash_fwd_pallas"),
                               ("flash-attention bwd", "_flash_bwd_pallas"),
                               ("fused RoPE", "_rope_pallas"),
                               ("fused int8 AdamW", "_fused_adamw_q8")):
            n = sum(marker in l for l in calls)
            check(f"{kernel} custom call in the compiled step", n > 0,
                  f"{n} calls")

    stats = jax.devices()[0].memory_stats() or {}
    say(phase, peak_bytes_in_use=stats.get("peak_bytes_in_use",
                                           "not reported"),
        bytes_limit=stats.get("bytes_limit", "not reported"))
    return device, check.failed


def multichip_phase(size=FULL, chip=True):
    """Tensor-parallel serve on four chips against the single-device
    engine in the same process.  Returns (device, failed_checks)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.models.llama_decode import _forward
    from paddle_tpu.ops.decode_attention import init_kv_cache
    from paddle_tpu.serving import ServingEngine

    phase = "multichip"
    device = start(phase, chip, n_devices=4)
    check = Checks(phase)
    if chip:
        check("jax.device_count() == 4", jax.device_count() == 4,
              jax.device_count())
    mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
    model = build_model(size)
    new = size["new_tokens"]
    prompts = make_prompts(size, "prompts", seed=1)

    engines, firsts = {}, {}
    for label, opts in (("single", {}), ("tp4", dict(mesh=mesh))):
        before = compile_events()
        eng = engines[label] = ServingEngine(model, **size["serve"], **opts)
        reqs, secs = run_wave(eng, prompts, new)
        say(f"{phase}/{label}", requests=len(reqs), seconds=round(secs, 2),
            tokens=sum(len(r.output_ids) for r in reqs),
            **compile_events(since=before))
        check(f"{label}: every request done with {new} tokens",
              all_done(reqs, new), [r.status for r in reqs])
        firsts[label] = [r.output_ids[0] for r in reqs]

    # placement: every engine leaf on four DIFFERENT devices; a leaf the TP
    # rules shard holds a quarter of its bytes on each, a replicated one
    # (embedding, lm_head, norms, rope tables) holds a whole copy on each
    tp = engines["tp4"]
    per_device = {}
    sharded = replicated = 0
    spread = quartered = True
    for leaf in engine_leaves(tp):
        shards = leaf.addressable_shards
        spread &= len({s.device for s in shards}) == 4
        for s in shards:
            per_device[str(s.device)] = per_device.get(str(s.device), 0) \
                + s.data.nbytes
        if leaf.sharding.is_fully_replicated:
            replicated += leaf.nbytes
        else:
            sharded += leaf.nbytes
            quartered &= all(s.data.nbytes * 4 == leaf.nbytes for s in shards)
    say(phase, per_device_bytes=per_device, sharded_leaf_bytes=sharded,
        replicated_leaf_bytes=replicated)
    check("every weight and pool leaf has shards on 4 different devices",
          spread)
    check("every sharded leaf holds 1/4 of its bytes per device", quartered)
    check("no device holds more of the engine than another",
          len(set(per_device.values())) == 1 and len(per_device) == 4)

    decode, _ = compiled_texts(tp)
    check("all-reduce in the compiled TP decode program",
          "all-reduce" in decode, f"{decode.count('all-reduce(')} ops")

    # first-token logits of the two engines' own placed weights through the
    # serving forward (GSPMD partitions it by the weights' shardings)
    _, nkv, hd, _ = tp._cfg
    n_layers = len(tp._params["layers"])
    dtype = tp._params["embed"].dtype

    @jax.jit
    def logits_of(params, tokens, last):
        mini = [init_kv_cache(1, tokens.shape[1], nkv, hd, dtype)
                for _ in range(n_layers)]
        return _forward(params, tp._cfg, tokens, mini,
                        jnp.zeros((1,), jnp.int32), last_only=True,
                        last_idx=last)[0]

    worst, flips = 0.0, 0
    for p, a, b in zip(prompts, firsts["single"], firsts["tp4"]):
        # right-padded to one compiled length; the pad is causally
        # invisible to the prompt's last position
        tokens = np.zeros((1, size["ref_len"]), np.int32)
        tokens[0, :len(p)] = p
        last = jnp.asarray([len(p) - 1], jnp.int32)
        one = np.asarray(
            logits_of(engines["single"]._params, tokens, last)[0], np.float32)
        four = np.asarray(logits_of(tp._params, tokens, last)[0], np.float32)
        worst = max(worst, float(np.abs(one - four).max()))
        flips += a != b and one[a] - one[b] > TP_LOGIT_TOL
    check(f"first-token logits agree within {TP_LOGIT_TOL}",
          np.isfinite(worst) and worst <= TP_LOGIT_TOL,
          f"max_abs_diff={worst:.4f}")
    check("first tokens agree (ties inside the tolerance aside)", flips == 0,
          f"single={firsts['single']} tp4={firsts['tp4']}")
    say(phase, **compile_events())
    for eng in engines.values():
        eng.close()
    return device, check.failed


PHASES = {"serve": serve_phase, "train": train_phase,
          "multichip": multichip_phase}


def child(name):
    """Run one phase; the last line is its JSON verdict."""
    device, failed = PHASES[name]()
    print(json.dumps({"phase": name, "ok": not failed, "failed": failed,
                      "device": device}), flush=True)
    return 0 if not failed else 1


# --------------------------------------------------------------- the parent
def run_child(name, deadline):
    """Start one phase in its own process (a fresh chip), pass its output
    through, and return its verdict; the process is gone on return (killed
    at the deadline if need be)."""
    proc = subprocess.Popen(
        [sys.executable, "-u", os.path.abspath(__file__), "--phase", name],
        stdout=subprocess.PIPE, text=True)
    timer = threading.Timer(max(1.0, deadline - time.monotonic()), proc.kill)
    timer.daemon = True
    timer.start()
    last = ""
    try:
        with proc.stdout as out:
            for line in out:
                print(line, end="", flush=True)
                if line.strip():
                    last = line.strip()
        rc = proc.wait()
    finally:
        timer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    try:
        verdict = json.loads(last)
    except ValueError:
        verdict = {}
    if rc != 0 or verdict.get("phase") != name:
        why = f"exit code {rc}" + (
            f" (killed: over the {BUDGET_S} s budget)"
            if time.monotonic() >= deadline else "")
        verdict = {"phase": name, "ok": False,
                   "failed": verdict.get("failed") or [why]}
    return verdict


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the tensor-parallel serve phase "
                         "and its single-device comparison")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return child(args.phase)

    deadline = time.monotonic() + BUDGET_S
    verdicts = [run_child(name, deadline)
                for name in (("multichip",) if args.multichip
                             else ("serve", "train"))]
    bad = {v["phase"]: v.get("failed") for v in verdicts if not v.get("ok")}
    if bad:
        print(json.dumps({"ok": False, "failed": bad}))
        return 1
    print(json.dumps({"ok": True, "device": verdicts[0]["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
