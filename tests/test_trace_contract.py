"""The names the measurement reads, held by tests (CPU, toy sizes).

The benchmark's per-layer readers find the device work of the three compiled
programs by name: the XLA module names derived from the jitted functions
(``serving_decode_steps``, ``serving_prefill_chunk``, ``_step_fn``), the
``jax.named_scope`` vocabulary of ``observability.trace`` in each operation's
``op_name``, and the host spans of the engine's phases on the profiler's
timeline.  A rename that silences a reader fails here first.
"""
import gc
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import falcon_h1_decode, glm4_moe_lite_decode
from paddle_tpu.models.falcon_h1 import FalconH1Config, FalconH1ForCausalLM
from paddle_tpu.models.glm4_moe_lite import (
    Glm4MoeLiteConfig, Glm4MoeLiteForCausalLM,
)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.llama_decode import (
    serving_decode_steps, serving_prefill_chunk,
)
from paddle_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM
from paddle_tpu.observability import compilecache
from paddle_tpu.observability.trace import (
    COUNTERS, EXPERT_SCOPES, LOOPS, RESIDUAL_SCOPES, SCOPES, SPANS, STAGES,
    STATE_SCOPES,
)
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.static.functionalize import build_train_step

from _xplane import host_events, inside, profiled

# substrings the benchmark's existing readers match device events by
# (benchmark/layer_metrics/*.py: MODULE / KERNEL patterns)
READER_PATTERNS = ("flash", "_step_fn", "serving_decode_steps",
                   "serving_prefill_chunk")
MODULE_NAMES = {"decode": "serving_decode_steps",
                "prefill": "serving_prefill_chunk", "train": "_step_fn",
                "ssm_decode": "serving_decode_steps",
                "ssm_prefill": "serving_prefill_chunk",
                "moe_decode": "serving_decode_steps",
                "moe_prefill": "serving_prefill_chunk",
                "hc_decode": "serving_decode_steps",
                "hc_prefill": "serving_prefill_chunk"}
SERVING = ("embed", "norm", "attn.qkv", "attn.rope", "attn.kv_write",
           "attn.core", "attn.out", "mlp", "lm_head", "sample",
           "attn.core.chunks")
# the state-space branch beside the attention branch (models/falcon_h1.py):
# the one-token update in the decode program, the chunked scan in prefill
SSM = tuple(n for n in STATE_SCOPES
            if n not in ("ssm.scan", "ssm.state_update"))
APPLIES = {
    "decode": SERVING + ("decode.steps",),
    "prefill": SERVING,
    "ssm_decode": SERVING + SSM + ("decode.steps", "ssm.state_update"),
    "ssm_prefill": SERVING + SSM + ("ssm.scan",),
    # latent attention + routed experts (models/glm4_moe_lite_decode.py):
    # the dense first layer keeps ``mlp``
    "moe_decode": SERVING + EXPERT_SCOPES + ("decode.steps",),
    "moe_prefill": SERVING + EXPERT_SCOPES,
    # the same two programs over a hyper-connected model (models/xing4.py):
    # GLM's names and the residual path's three, which GLM's carry none of
    "hc_decode": SERVING + EXPERT_SCOPES + RESIDUAL_SCOPES
    + ("decode.steps",),
    "hc_prefill": SERVING + EXPERT_SCOPES + RESIDUAL_SCOPES,
    "train": ("embed", "norm", "attn.qkv", "attn.rope", "attn.core",
              "attn.out", "mlp", "lm_head", "loss", "optimizer"),
}
PROMPTS = [np.arange(1, 1 + n, dtype=np.int32) % 250 + 1 for n in (21, 9, 30)]
NEW = (5, 7, 4)


def tiny_model(seed=0, **kw):
    paddle.seed(seed)
    model = LlamaForCausalLM(LlamaConfig.tiny(dtype="float32", **kw))
    model.eval()
    return model


def tiny_engine(**kw):
    # chunks narrower than the cache, so the chunked read (and its loop)
    # is the path taken, as at the benchmark's sizes
    return ServingEngine(tiny_model(), batch_size=2, max_len=64,
                         prefill_chunk=16, decode_chunk=16, **kw)


def tiny_ssm_engine(**kw):
    paddle.seed(0)
    model = FalconH1ForCausalLM(FalconH1Config.tiny())
    model.eval()
    return ServingEngine(model, batch_size=2, max_len=64, prefill_chunk=16,
                         decode_chunk=16, **kw)


def tiny_moe_engine(**kw):
    paddle.seed(0)
    model = Glm4MoeLiteForCausalLM(Glm4MoeLiteConfig.tiny())
    model.eval()
    return ServingEngine(model, batch_size=2, max_len=64, prefill_chunk=16,
                         decode_chunk=16, **kw)


def tiny_hc_engine(**kw):
    paddle.seed(0)
    model = Xing4ForCausalLM(Xing4Config.tiny())
    model.eval()
    return ServingEngine(model, batch_size=2, max_len=64, prefill_chunk=16,
                         decode_chunk=16, **kw)


def tiny_train_step(seed=0):
    paddle.seed(seed)
    model = LlamaForCausalLM(LlamaConfig.tiny(
        dtype="float32", recompute=True, loss_chunk_size=32))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    ids = paddle.to_tensor(
        np.random.default_rng(seed).integers(0, 256, (2, 32)), dtype="int64")
    return build_train_step(model, None, opt), ids


@pytest.fixture(scope="module")
def lowered():
    """The three programs, lowered as their callers run them."""
    eng = tiny_engine()
    rows, scalar = jnp.zeros((2,), jnp.int32), jnp.int32(0)
    step, ids = tiny_train_step()
    ssm = tiny_ssm_engine()
    glm = tiny_moe_engine()
    hc = tiny_hc_engine()
    return {
        "hc_decode":
            glm4_moe_lite_decode.serving_decode_steps.__wrapped__.lower(
                hc._params, hc._cfg, rows, hc._kv.caches, rows,
                n_steps=hc._sync, chunk_size=hc._chunk, block_tables=None,
                program_key=hc._pk),
        "hc_prefill":
            glm4_moe_lite_decode.serving_prefill_chunk.__wrapped__.lower(
                hc._params, hc._cfg, jnp.zeros((1, 16), jnp.int32), scalar,
                jnp.zeros((1,), jnp.int32), hc._kv.caches, scalar,
                hist=None, hist_len=None, with_hist=False,
                chunk_size=hc._chunk, block_tables=None,
                program_key=hc._pk),
        "moe_decode":
            glm4_moe_lite_decode.serving_decode_steps.__wrapped__.lower(
                glm._params, glm._cfg, rows, glm._kv.caches, rows,
                n_steps=glm._sync, chunk_size=glm._chunk, block_tables=None,
                program_key=glm._pk),
        "moe_prefill":
            glm4_moe_lite_decode.serving_prefill_chunk.__wrapped__.lower(
                glm._params, glm._cfg, jnp.zeros((1, 16), jnp.int32), scalar,
                jnp.zeros((1,), jnp.int32), glm._kv.caches, scalar,
                hist=None, hist_len=None, with_hist=False,
                chunk_size=glm._chunk, block_tables=None,
                program_key=glm._pk),
        "ssm_decode": falcon_h1_decode.serving_decode_steps.__wrapped__.lower(
            ssm._params, ssm._cfg, rows, ssm._kv.caches, rows,
            n_steps=ssm._sync, chunk_size=ssm._chunk, block_tables=None,
            program_key=ssm._pk),
        "ssm_prefill":
            falcon_h1_decode.serving_prefill_chunk.__wrapped__.lower(
                ssm._params, ssm._cfg, jnp.zeros((1, 16), jnp.int32), scalar,
                jnp.zeros((1,), jnp.int32), ssm._kv.caches, scalar,
                hist=None, hist_len=None, with_hist=False,
                chunk_size=ssm._chunk, block_tables=None,
                program_key=ssm._pk),
        "decode": serving_decode_steps.__wrapped__.lower(
            eng._params, eng._cfg, rows, eng._kv.caches, rows,
            n_steps=eng._sync, chunk_size=eng._chunk, block_tables=None,
            program_key=eng._pk),
        "prefill": serving_prefill_chunk.__wrapped__.lower(
            eng._params, eng._cfg, jnp.zeros((1, 16), jnp.int32), scalar,
            jnp.zeros((1,), jnp.int32), eng._kv.caches, scalar, hist=None,
            hist_len=None, with_hist=False, chunk_size=eng._chunk,
            block_tables=None, program_key=eng._pk),
        "train": step.lower(ids, ids),
    }


@pytest.fixture(scope="module")
def op_names(lowered):
    """Every ``op_name`` of each COMPILED program's HLO."""
    return {k: set(re.findall(r'op_name="([^"]+)"',
                              lo.compile().as_text()))
            for k, lo in lowered.items()}


def components(path):
    """The scope names in an operation's path, outermost first: each
    ``/``-separated part stripped of the transforms JAX wraps it in
    (``transpose(jvp(attn.core))`` -> ``attn.core``)."""
    return [re.sub(r"^(?:\w+\()*|\)*$", "", part) for part in path.split("/")]


# (a) the module names the readers match
@pytest.mark.parametrize("program", sorted(MODULE_NAMES))
def test_module_name_holds_the_readers_pattern(lowered, program):
    head = lowered[program].as_text().split("\n", 1)[0]
    assert MODULE_NAMES[program] in re.search(r"module @(\S+)", head).group(1)


# (b) the vocabulary reaches the compiled operations
@pytest.mark.parametrize("program,name", [
    (p, n) for p in sorted(APPLIES) for n in APPLIES[p]])
def test_scope_names_the_compiled_operations(op_names, program, name):
    assert any(name in components(path) for path in op_names[program]), \
        f"no operation of the {program} program carries {name!r}"


@pytest.mark.parametrize("program", sorted(APPLIES))
def test_program_carries_no_name_outside_its_list(op_names, program):
    found = {c for path in op_names[program] for c in components(path)
             if c in SCOPES + STATE_SCOPES + EXPERT_SCOPES + RESIDUAL_SCOPES
             + LOOPS}
    assert found == set(APPLIES[program])


def test_loops_are_named(op_names):
    """A %while of a device trace can be told: the cache-chunk loop's own
    ``while`` sits under its name, and the step's operations under the
    scan's (at ``sync_every=1`` XLA takes the one-trip loop itself away)."""
    for program in ("decode", "ssm_decode", "moe_decode", "hc_decode"):
        assert any("decode.steps/while/body/" in path
                   for path in op_names[program])
    for program in ("decode", "prefill", "ssm_decode", "ssm_prefill",
                    "moe_decode", "moe_prefill", "hc_decode", "hc_prefill"):
        assert any(path.endswith("attn.core.chunks/while")
                   for path in op_names[program])


def test_latent_read_is_named_at_its_call(op_names):
    """What the TPU compiler makes at ``decode_attention``'s jit boundary
    (and of a flat view's gather: a loop of window copies, PERF.md section
    5, PR 33 — since PR 36 only for a span the rows' groups do not divide)
    keeps the path of the CALL and loses the scopes inside it: the scope
    the model opens around the call is what names it."""
    inside = [p for p in op_names["moe_decode"] if "jit(decode_attention)" in p]
    assert inside and all(
        "attn.core" in components(p.split("jit(decode_attention)")[0])
        for p in inside)


def test_whole_sequence_forward_of_a_hyper_connected_model_is_named():
    """``Xing4ForCausalLM.forward`` (the plain model forward, expanded
    attention) opens the residual path's three scopes too; GLM's forward
    opens none of them."""
    def paths(model):
        from paddle_tpu.models.glm4_moe_lite import (block_forward,
                                                     rope_tables, statics_of,
                                                     stream_in)

        cfg = statics_of(model.config)
        lp = {k: v.data for k, v in model.model.layers[-1].weights().items()}
        cos_t, sin_t = rope_tables(model.config, 8, "float32")
        h = stream_in(cfg, jnp.zeros((1, 8, model.config.hidden_size),
                                     jnp.float32))
        text = jax.jit(lambda h: block_forward(lp, cfg, h, cos_t, sin_t)
                       ).lower(h).compile().as_text()
        return {c for path in re.findall(r'op_name="([^"]+)"', text)
                for c in components(path)}

    paddle.seed(0)
    assert set(RESIDUAL_SCOPES) <= paths(Xing4ForCausalLM(Xing4Config.tiny()))
    assert not set(RESIDUAL_SCOPES) & paths(
        Glm4MoeLiteForCausalLM(Glm4MoeLiteConfig.tiny()))


def test_backward_and_recompute_keep_the_forwards_names(op_names):
    paths = op_names["train"]
    for name in ("attn.core", "mlp", "loss"):
        assert any("transpose(" in p and name in components(p)
                   for p in paths), f"no backward operation under {name!r}"
    assert any("rematted_computation" in p and "mlp" in components(p)
               for p in paths)


@pytest.mark.parametrize("name", SCOPES + STATE_SCOPES + EXPERT_SCOPES
                         + RESIDUAL_SCOPES + LOOPS + SPANS)
def test_vocabulary_avoids_the_readers_patterns(name):
    assert not any(pat in name or name in pat for pat in READER_PATTERNS)
    assert re.fullmatch(r"[a-z_]+(\.[a-z_]+)*", name)


def test_applies_covers_the_vocabulary():
    assert set().union(*map(set, APPLIES.values())) \
        == set(SCOPES + STATE_SCOPES + EXPERT_SCOPES + RESIDUAL_SCOPES
               + LOOPS)


def test_state_counters_are_the_names_the_readers_ask_for():
    """``serving_state_bytes`` / ``serving_state_resets_total``: what a
    model with recurrent state beside its K/V rows adds to the registry."""
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    eng = tiny_ssm_engine(registry=reg)
    eng.submit(Request(PROMPTS[0], 3))
    eng.run()
    lbl = dict(policy="continuous")
    assert reg.get("serving_state_bytes").labels(**lbl).value > 0
    assert reg.get("serving_state_resets_total").labels(**lbl).value == 1


def test_expert_counters_are_the_names_the_readers_ask_for():
    """``serving_moe_*``: what a model with routed experts adds to the
    registry — pairs by expert, experts touched and counted runs by
    program —, fed from the routes its programs hand back."""
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    eng = tiny_moe_engine(registry=reg)
    r = eng.submit(Request(PROMPTS[0], 3))
    eng.run()
    snap = reg.snapshot()
    by = lambda name, label: {s["labels"][label]: s["value"]
                              for s in snap[name]["series"]}
    pairs = by("serving_moe_expert_tokens_total", "expert")
    assert set(pairs) <= {str(e) for e in range(8)}
    rows = sum(len(x) for x in r.routes)
    assert rows == len(PROMPTS[0]) + 3 - 1
    assert sum(pairs.values()) >= rows * 2 * 2          # 2 layers x top-2
    # 21 rows are two 16-row chunks in ONE run: one record, its real rows
    assert by("serving_moe_dispatches_total", "program")["prefill"] == 1
    assert len(r.routes[0]) == len(PROMPTS[0])
    assert by("serving_moe_experts_touched_total", "program")["decode"] > 0


@pytest.mark.parametrize("name", COUNTERS)
def test_engine_registers_every_counter_of_the_list(name):
    """Every ``serving_*`` name of ``trace.COUNTERS`` is a series an
    instrumented engine registers at construction, before a request has
    run; the compile stages' seconds are the process's (the one listener
    feeds the default registry, whatever registry an engine was given)."""
    from paddle_tpu.observability.metrics import (
        MetricsRegistry, get_registry,
    )

    reg = MetricsRegistry()
    tiny_engine(registry=reg)
    if not name.startswith("serving_"):
        reg = get_registry()
    assert reg.get(name) is not None


def test_kv_rows_counters_read_the_numbers_computed_by_hand():
    """``serving_kv_rows_read_total`` / ``serving_kv_rows_live_total`` on a
    stream with known lengths: one request of 21 prompt tokens and 5 new
    ones in a 2-slot engine with 16-row chunks.  The first token comes from
    the prefill; the four decode dispatches run at lengths 21..24 (the
    other slot parked), each attending to length + 1 rows and — two slots
    being one block, the batch-wide rule — reading ``ceil((length + 1) /
    16) = 2`` chunks of both slots: 4 x 2 x 2 x 16 rows read, 22 + 23 + 24
    + 25 live = 256 and 94.  The pipelined engine has dispatched a fifth
    step (length 25) before the fourth's tokens tell it the request is
    done: 64 and 26 more."""
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    eng = tiny_engine(registry=reg)
    eng.submit(Request(PROMPTS[0], NEW[0]))
    eng.run()
    lbl = dict(policy="continuous")
    assert reg.get("serving_kv_rows_read_total").labels(**lbl).value == 320
    assert reg.get("serving_kv_rows_live_total").labels(**lbl).value == 120


def test_chunks_and_runs_read_the_numbers_computed_by_hand():
    """``serving_prefill_chunks_total`` counts 16-row chunks, ``_runs_total``
    runs of the program, on a hand-made arrival: 40 rows (3 chunks) and 50
    rows (4 chunks) admitted together, two chunks a step.  Step 1: A's
    chunks 0-1 in one run.  Step 2: A's odd last chunk alone, and B's first
    takes the chunk the step has left — alone too.  Step 3: B's chunks 1-2
    in one run.  Step 4: B's last.  7 chunks in 5 runs; a run of k chunks
    leaves k ``prefilling`` marks with consecutive indices, ``final`` on
    the chunk that holds the prompt's last row; the backlog gauge counts
    chunks."""
    from paddle_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    eng = tiny_engine(registry=reg)
    a, b = (eng.submit(Request(np.arange(1, 1 + n, dtype=np.int32), 3))
            for n in (40, 50))
    lbl = dict(policy="continuous")
    value = lambda name: reg.get(name).labels(**lbl).value
    eng.step()
    assert (value("serving_prefill_chunks_total"),
            value("serving_prefill_runs_total"),
            value("serving_prefill_backlog")) == (2, 1, 1 + 4)
    eng.run()
    assert value("serving_prefill_chunks_total") == 7
    assert value("serving_prefill_runs_total") == 5
    runs = [(e["rid"], e["chunk"], e["chunks"], e["final"])
            for e in eng.recorder.events() if e["kind"] == "prefill_chunk"]
    assert runs == [(a.rid, 0, 2, False), (a.rid, 2, 1, True),
                    (b.rid, 0, 1, False), (b.rid, 1, 2, False),
                    (b.rid, 3, 1, True)]
    for r, n in ((a, 3), (b, 4)):
        marks = [m for m in r.timeline() if "chunk" in m]
        assert [m["chunk"] for m in marks] == list(range(n))
        assert [m["final"] for m in marks] == [False] * (n - 1) + [True]
        assert all(m["phase"] == "prefilling" for m in marks)
        assert marks[-1]["t"] <= r.t_first


# (c) the host spans, on the profiler's timeline
@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """An engine and a train step built, a few engine steps, two train
    steps and a full collection under ONE profiler session: (spans of the
    xplane, the engine's flight-recorder events)."""
    trace_dir = tmp_path_factory.mktemp("trace")
    with profiled(trace_dir):
        eng = tiny_engine()
        step, ids = tiny_train_step()
        for p, n in zip(PROMPTS, NEW):
            eng.submit(Request(p, n))
        eng.run()
        step(ids, ids)
        step(ids, ids)
        gc.collect()
    return (host_events(trace_dir, ["serving.", "train.", "host."]),
            eng.recorder.events())


# ``import`` is the one phase that is stamped, not opened: it ends before a
# profiler session could hold it
@pytest.mark.parametrize("name", [n for n in SPANS if n != "import"])
def test_span_is_an_event_of_the_xplane(traced, name):
    assert [e for e in traced[0] if e["name"] == name]


@pytest.mark.parametrize("child,parent", [
    ("serving.admit", "serving.step"),
    ("serving.spend_prefill", "serving.step"),
    ("serving.prefill_chunk", "serving.spend_prefill"),
    ("serving.dispatch", "serving.step"),
    ("serving.drain", "serving.step"),
    ("serving.drain.wait", "serving.drain"),
    ("serving.emit", "serving.drain"),
    ("serving.init.params", "serving.init"),
    ("serving.init.cache", "serving.init"),
])
def test_spans_nest_and_share_their_step(traced, child, parent):
    parents = [e for e in traced[0] if e["name"] == parent]
    for c in (e for e in traced[0] if e["name"] == child):
        mine = [p for p in parents if inside(c, p)]
        assert len(mine) == 1, f"{child} at {c['start']} not in one {parent}"
        assert str(c["step"]) == str(mine[0]["step"])


def test_spans_of_one_request_share_its_rid(traced):
    spans = traced[0]
    for rid in range(len(PROMPTS)):
        mine = [e["name"] for e in spans if str(e.get("rid")) == str(rid)]
        assert mine[0] == "serving.submit" and mine[1] == "serving.admit"
        assert set(mine[2:]) == {"serving.prefill_chunk"}
    runs = [e for e in spans if e["name"] == "serving.prefill_chunk"]
    # a span a RUN: 21, 9 and 30 tokens in chunks of 16, two a step, are
    # 2 + 1 + 2 chunks in three runs, each holding its prompt's last row
    assert [(int(e["chunk"]), int(e["chunks"])) for e in runs] == [
        (0, 2), (0, 1), (0, 2)]
    assert sum(int(e["final"]) for e in runs) == 3


def test_train_spans_carry_their_step(traced):
    steps = [e for e in traced[0] if e["name"] == "train.step"]
    assert [int(e["step"]) for e in steps] == [1, 2]


def test_every_serving_span_has_its_flight_recorder_event(traced):
    """One call marks both, so they cannot drift: the same boundaries, the
    same steps, the same count."""
    spans, events = traced
    want = sorted((e["name"][len("serving."):], int(e["step"]))
                  for e in spans if e["name"].startswith("serving."))
    kinds = {name[len("serving."):] for name in SPANS
             if name.startswith("serving.")}
    got = sorted((e["kind"], e["step"]) for e in events
                 if e["kind"] in kinds)
    assert got == want
    assert all(e["seconds"] >= 0 for e in events if e["kind"] in kinds)


def test_final_chunk_is_marked_on_the_timeline():
    eng = tiny_engine()
    r = eng.submit(Request(PROMPTS[0], 3))
    eng.run()
    chunks = [m for m in r.timeline() if "chunk" in m]
    assert [m["chunk"] for m in chunks] == [0, 1]
    assert [m["final"] for m in chunks] == [False, True]
    first = [m["t"] for m in r.timeline() if m["phase"] == "decoding"][0]
    assert chunks[-1]["t"] <= r.t_first <= first


# (c') the start-up record: compile stages by program, phases, collector
# pauses (observability/compilecache.py), each test on a log of its own —
# the process's has whatever the worker's earlier tests compiled
@pytest.fixture
def startup(monkeypatch):
    log = compilecache.StartupLog()
    monkeypatch.setattr(compilecache, "startup", log)
    return log


def monitored_program():
    """A fresh monitor over a fresh jit (nothing else has traced it) whose
    body calls an inner jit, as the serving programs do."""
    mon = compilecache.CompileCacheMonitor("contract")

    @jax.jit
    def _contract_inner(x):
        return x * 2.0

    @jax.jit
    def _contract_program(x):
        mon.mark_trace("program")
        return _contract_inner(x) + 1.0

    return mon, _contract_program


def test_a_miss_leaves_its_stages_under_one_first_call(startup):
    from paddle_tpu.observability.metrics import get_registry

    mon, fn = monitored_program()
    hist = get_registry().get("compile_seconds").labels(
        cache="contract", program="program")
    seconds_before = hist.sum
    x = jnp.arange(7.0)
    jax.block_until_ready(x)
    startup_before = len(startup.entries())
    t0 = time.perf_counter()
    mon.call("program", fn, x)
    t1 = time.perf_counter()
    mine = startup.entries()[startup_before:]
    assert [e["stage"] for e in mine] == ["trace", "lower", "load",
                                          "first_call"]
    assert all((e["cache"], e["program"]) == ("contract", "program")
               for e in mine)
    trace, lower, load, first = mine
    # the drivers' clock, in order, the stages inside the dispatch
    assert t0 <= first["t_start"] <= trace["t_start"] <= trace["t_end"] \
        <= lower["t_end"] <= load["t_end"] <= first["t_end"] <= t1
    assert lower["t_start"] >= trace["t_end"] - 1e-4
    assert load["t_start"] >= lower["t_end"] - 1e-4
    assert len({e["tid"] for e in mine}) == 1
    # the inner jit's trace is folded into the program's, JAX's names kept
    assert trace["inner"] >= 1 and "_contract_program" in trace["fun_name"]
    assert "_contract_program" in load["fun_name"]
    # ``compile_seconds`` and ``first_call`` are one measurement
    assert hist.sum - seconds_before == pytest.approx(
        first["t_end"] - first["t_start"])
    # the second call is a hit: two stores, no entry
    mon.call("program", fn, x)
    assert len(startup.entries()) == startup_before + 4
    stages = get_registry().get("compile_stage_seconds_total")
    for e in (trace, lower, load):
        assert stages.labels(cache="contract", program="program",
                             stage=e["stage"]).value > 0


def test_a_compile_outside_a_monitored_call_keeps_jaxs_name(startup):
    compilecache.CompileCacheMonitor("contract")     # the listener is on

    @jax.jit
    def _contract_helper(x):
        return x - 3.0

    _contract_helper(jnp.arange(5.0))
    mine = [e for e in startup.entries()
            if "_contract_helper" in e.get("fun_name", "")]
    assert [e["stage"] for e in mine] == ["trace", "lower", "load"]
    assert all((e["cache"], e["program"]) == ("-", "-") for e in mine)
    assert not [e for e in startup.entries() if e["stage"] == "first_call"]


def test_the_log_keeps_its_first_entries_when_full():
    log = compilecache.StartupLog(capacity=3)
    for k in range(5):
        log.add({"stage": "phase", "name": f"p{k}", "t_start": k,
                 "t_end": k + 1, "parent": None, "tid": 0})
    assert [e["name"] for e in log.entries()] == ["p0", "p1", "p2"]
    assert log.dropped == 2


def test_inner_traces_fold_across_what_lies_between_them():
    """A collection (or a helper's load) that ends between two inner
    traces does not stop the fold: the outer trace takes both."""
    log = compilecache.StartupLog()
    mk = lambda stage, t0, t1, **kw: dict(
        stage=stage, cache="c", program="p", fun_name=stage, t_start=t0,
        t_end=t1, parent=None, tid=7, **kw)
    log.add_stage(mk("trace", 0.1, 0.2))                # an earlier program
    log.add_stage(mk("trace", 1.0, 1.2, inner=3))
    log.add({"stage": "phase", "name": "host.gc", "t_start": 1.3,
             "t_end": 1.4, "parent": None, "tid": 7})
    log.add_stage(mk("load", 1.45, 1.5))
    log.add_stage(dict(mk("trace", 1.5, 1.6), tid=8))   # another thread's
    log.add_stage(mk("trace", 1.5, 1.6))
    own = log.add_stage(mk("trace", 0.9, 2.0))
    assert own == pytest.approx(1.1 - 0.2 - 0.1)
    assert [(e["stage"], e["t_start"], e.get("inner"), e["tid"])
            for e in log.entries()] == [
        ("trace", 0.1, None, 7), ("phase", 1.3, None, 7),
        ("load", 1.45, None, 7), ("trace", 1.5, None, 8),
        ("trace", 0.9, 5, 7)]


def test_construction_phases_are_entries_with_their_parents(startup):
    eng = tiny_engine()
    tiny_train_step()
    by = {e["name"]: e for e in startup.entries() if e["stage"] == "phase"}
    assert {"serving.init", "serving.init.params", "serving.init.cache",
            "train.build"} <= set(by)
    init = by["serving.init"]
    for child in ("serving.init.params", "serving.init.cache"):
        assert by[child]["parent"] == "serving.init"
        assert init["t_start"] <= by[child]["t_start"] \
            <= by[child]["t_end"] <= init["t_end"]
    assert by["serving.init"]["parent"] is by["train.build"]["parent"] is None
    # a compile inside a phase names the phase that was open
    @jax.jit
    def _contract_inside(x):
        return x + 5.0

    with compilecache.phase("train.build"):
        _contract_inside(jnp.arange(4.0))
    inside = [e for e in startup.entries()
              if "_contract_inside" in e.get("fun_name", "")]
    assert len(inside) == 3
    assert all(e["parent"] == "train.build" for e in inside)
    kinds = [e["kind"] for e in eng.recorder.events()]
    assert kinds[:3] == ["init.params", "init.cache", "init"]


def test_a_full_collection_is_one_host_gc_entry(startup):
    compilecache.CompileCacheMonitor("contract")     # the callback is on
    pauses = lambda: sum(e.get("name") == "host.gc"
                         for e in startup.entries())
    gc.collect()
    n = pauses()
    gc.collect(0)
    gc.collect(1)
    assert pauses() == n
    gc.collect()
    assert pauses() == n + 1 >= 2


def test_every_name_of_the_record_is_in_the_vocabulary(startup):
    mon, fn = monitored_program()
    mon.call("program", fn, jnp.arange(3.0))
    tiny_engine()
    tiny_train_step()
    gc.collect()
    seen = startup.entries()
    assert {e["stage"] for e in seen} == set(STAGES) - {"cache_retrieval"} \
        | {"phase"}
    assert {e["name"] for e in seen if e["stage"] == "phase"} <= set(SPANS)
    assert "compile_stage_seconds_total" in COUNTERS
    rows = compilecache.report()
    assert rows == sorted(rows, key=lambda r: -r["seconds"])
    assert {"kind": "program", "name": "contract/program",
            "stage": "first_call"}.items() <= {
        k: v for r in rows if r["name"] == "contract/program"
        and r["stage"] == "first_call" for k, v in r.items()}.items()


def test_the_packages_import_is_the_first_entry():
    first = compilecache.startup.entries()[0]
    assert (first["stage"], first["name"]) == ("phase", "import")
    assert first["t_end"] > first["t_start"]
    assert isinstance(first["detail"]["jax_imported_before"], bool)


# (d) tracing changes nothing that is served or learned
def served(**kw):
    eng = tiny_engine(**kw)
    reqs = [eng.submit(Request(p, n)) for p, n in zip(PROMPTS, NEW)]
    eng.run()
    return [list(r.output_ids) for r in reqs]


def losses(n=3):
    step, ids = tiny_train_step()
    return [float(step(ids, ids).numpy()) for _ in range(n)]


def test_served_tokens_do_not_depend_on_instrumentation(tmp_path):
    plain = served(instrument=False, recorder=False)
    assert served() == plain
    with profiled(tmp_path):
        assert served() == plain


def test_losses_do_not_depend_on_a_profiler_session(tmp_path):
    plain = losses()
    with profiled(tmp_path):
        assert losses() == plain
    assert len(set(plain)) == 3


def test_scopes_leave_the_programs_operations_unchanged():
    """Scopes exist at trace time only: with them made no-ops the lowered
    decode program is the same text, debug locations aside."""
    import contextlib
    import unittest.mock

    def text():
        eng = tiny_engine()
        rows = jnp.zeros((2,), jnp.int32)
        # a fresh jit: the module-level one would answer from its cache
        return jax.jit(
            serving_decode_steps.__wrapped__.__wrapped__,
            static_argnames=("cfg", "n_steps", "chunk_size", "program_key"),
        ).lower(eng._params, eng._cfg, rows, eng._kv.caches, rows,
                n_steps=1, chunk_size=eng._chunk, block_tables=None,
                program_key=eng._pk).as_text()

    scoped = text()
    with unittest.mock.patch.object(
            jax, "named_scope", lambda name: contextlib.nullcontext()):
        assert text() == scoped


# what the benchmark's train driver and chip_smoke.py used to read through
# ``step._params`` / ``step._states`` / ``step._jitted``
def test_train_step_accessors_show_the_live_state():
    step, ids = tiny_train_step()
    before = {k: np.asarray(v) for k, v in step.params.items()}
    assert set(step.optimizer_states) >= {"moment1", "moment2"}
    assert all(not np.asarray(m).any()
               for m in step.optimizer_states["moment1"].values())
    step(ids, ids)
    after = step.params
    assert set(after) == set(before)
    assert any((np.asarray(after[k]) != before[k]).any() for k in before)
    assert any(np.asarray(m).any()
               for m in step.optimizer_states["moment1"].values())
    # read-only views: the mappings are copies, the step keeps its own
    after.clear()
    step.optimizer_states["moment1"].clear()
    assert step.params and step.optimizer_states["moment1"]
    assert not hasattr(type(step).params, "fset") \
        or type(step).params.fset is None


def test_train_step_lower_is_the_program_the_steps_run():
    step, ids = tiny_train_step()
    n0 = step._step_count
    lowered = step.lower(ids, ids)
    assert step._step_count == n0          # nothing ran, nothing counted
    assert "_step_fn" in lowered.as_text().split("\n", 1)[0]
    loss = float(step(ids, ids).numpy())
    assert np.isfinite(loss)
