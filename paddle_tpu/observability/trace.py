"""Span tracing: one primitive, one timeline, one vocabulary of names.

``span("serving.step", step=7)`` is a context manager *and* a decorator.
Entering it enters a ``jax.profiler.TraceAnnotation`` of the same name, so
the span is an event on the host plane of the SAME xplane that holds the
device lines — one clock for the program's phases and the device's work.
Keyword details (``step=``, ``rid=``, ``slot=``, ``chunk=``, ``n_live=``)
become the annotation's arguments (event stats in the xplane): the spans
of one request share its ``rid``, and nesting on a thread gives each span
its parent.  With no profiler session an annotation is a flag test; a span
writes to no histogram and to no list.  To see the spans: run under
``jax.profiler.trace(dir)`` (or ``start_trace`` / ``stop_trace``) and open
the trace, or read it with ``jax.profiler.ProfileData``.

The device side of the same vocabulary is ``jax.named_scope``:
:data:`SCOPES` names the model components and :data:`LOOPS` the two
compiled loops, and every reader and test takes the names from here.
Scopes exist at trace time only — they label the operations of a compiled
program (the ``op_name`` of each HLO instruction) and change none.

``jax`` is imported lazily, on the first span entered: importing this
module stays stdlib-only.
"""
from __future__ import annotations

import functools

__all__ = ["span", "chrome_event", "SCOPES", "STATE_SCOPES", "EXPERT_SCOPES",
           "RESIDUAL_SCOPES", "LOOPS", "SPANS", "STAGES", "COUNTERS",
           "ATTN_RESIDUALS"]

# model components, the same names in the serving programs
# (models/llama_decode.py, ops/decode_attention.py) and the training model
# (models/llama.py, ops/chunked_ce.py, static/functionalize.py).  Backward
# and recompute need no names of their own: JAX wraps the forward's scope
# as transpose(jvp(<scope>)) and puts rematted_computation in the path.
SCOPES = ("embed", "norm", "attn.qkv", "attn.rope", "attn.kv_write",
          "attn.core", "attn.out", "mlp", "lm_head", "sample", "loss",
          "optimizer")
# the state-space branch that runs beside the attention branch in a hybrid
# block (models/falcon_h1.py, ops/ssm.py): ssm.scan is the chunked scan of
# prefill and of the whole-sequence forward, ssm.state_update the one-token
# update of decode.  A tuple of its own: ``SCOPES + LOOPS`` is the list the
# accepted benchmark's reduction holds a copy of (benchmark/lib/
# span_reduce.NAMES); the cell that runs these reduces with both
# (benchmark/lib/falcon_h1_reduce.py).
STATE_SCOPES = ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.state_update",
                "ssm.norm_gate", "ssm.out")
# a routed expert FFN (ops/moe.py: the router, ordering and gathering the
# (token, expert) pairs, the grouped products, the combine; moe.shared the
# shared expert) and latent attention's absorbed products (mla.absorb: W_uk
# folded into the query and W_uv applied to the latent-wide result in
# models/glm4_moe_lite_decode.py; the cached rows' up-projection in the
# whole-sequence forward).  The projections, rope, the latent write and the
# core stay under the attn.* names above.  A tuple of its own for the reason
# STATE_SCOPES is (benchmark/lib/glm4_moe_lite_reduce.py reduces with both).
EXPERT_SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine",
                 "moe.shared", "mla.absorb")
# a hyper-connected residual path (ops/hyper_connection.py: a token's state
# is n streams; every sub-layer computes its mixing coefficients — the
# flattened norm, one small float32 product, the clamped Sinkhorn iteration —,
# reads the branch's input as a weighted sum of the streams and writes the
# branch's output back into all of them).  Used by both serving programs and
# the whole-sequence forward of a model whose ``hc_mult`` is over 1
# (models/glm4_moe_lite.py); a model with one stream opens none of them.  A
# tuple of its own for the reason STATE_SCOPES is
# (benchmark/lib/xing4_reduce.py reduces with these too).
RESIDUAL_SCOPES = ("hc.coeff", "hc.read", "hc.write")
# the compiled loops, so that a %while in a device trace can be told: the
# n_steps scan of the decode program and the cache-chunk loop of the
# chunked attention read
LOOPS = ("decode.steps", "attn.core.chunks")
# what the flash-attention backward reads of its forward, in the order of the
# custom VJPs' residual tuples (ops/flash_attention.py: q, k, v, out, lse).
# Each forward rule passes them through jax.ad_checkpoint.checkpoint_name
# under these names — the identity everywhere but inside a jax.checkpoint
# whose policy asks for a name, where a kept ``out`` + ``lse`` let the
# recompute drop the forward kernel (models/llama.py picks the kept set)
ATTN_RESIDUALS = ("attn.res.q", "attn.res.k", "attn.res.v", "attn.res.out",
                  "attn.res.lse")
# host spans: the engine's phases (serving/engine.py::_phase) and the
# train step's dispatch (static/functionalize.py); then the phases of the
# start-up record (observability/compilecache.py::phase — a span AND an
# entry of the in-memory log): an engine's construction, its weights pytree
# and its cache leaves, the train step's construction, a generation-2
# collection, and the package's import — the one that is stamped, not
# opened (it ends before a profiler session could hold it)
SPANS = ("serving.submit", "serving.step", "serving.admit",
         "serving.spend_prefill", "serving.prefill_chunk",
         "serving.dispatch", "serving.drain", "serving.drain.wait",
         "serving.emit", "train.step",
         "serving.init", "serving.init.params", "serving.init.cache",
         "train.build", "host.gc", "import")
# the compile stages of the start-up record, one entry an event of JAX's
# (observability/compilecache.py): tracing to a jaxpr, lowering it to a
# module, the backend's compile-or-load and, inside that, the persistent
# cache's retrieval; ``first_call`` is the monitored dispatch that held them
STAGES = ("trace", "lower", "load", "cache_retrieval", "first_call")

# the engine's counters and gauges that a measurement reads
# (serving/metrics.py): steps, tokens and prefill chunks (the benchmark's
# decode_batch_mean and window record), the runs of the prefill program
# that spent those chunks (chunks / runs: the chunks one read of the
# weights served), the recurrent state beside the K/V rows and the slots
# whose state one layer's decode update reads against those it skips
# (ops.ssm.ssm_state_update), and the decode cache read against the rows
# it needs — one layer's count at each decode dispatch's host lengths by
# the read's own rule (ops.decode_attention.kv_rows_read); read / live is
# the over-read; and
# what a routed expert FFN served: live (token, expert) pairs by expert,
# experts touched and counted runs by program (decode / prefill); and the
# process-wide seconds of the compile stages by program
# (observability/compilecache.py, the default registry)
COUNTERS = ("serving_steps_total", "serving_tokens_emitted_total",
            "serving_prefill_chunks_total", "serving_prefill_runs_total",
            "serving_state_bytes", "serving_state_resets_total",
            "serving_state_slots_read_total",
            "serving_state_slots_skipped_total",
            "serving_kv_rows_read_total", "serving_kv_rows_live_total",
            "serving_moe_expert_tokens_total",
            "serving_moe_experts_touched_total",
            "serving_moe_dispatches_total",
            "compile_stage_seconds_total")

SPAN_EVENT_TYPE = "Span"

_annotation = None


def _trace_annotation():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


def chrome_event(name, start_ns, end_ns, *, tid, event_type=SPAN_EVENT_TYPE,
                 args=None):
    """One chrome-trace event dict in the shape ``paddle.profiler`` exports
    (``RecordEvent`` scopes): what the flight recorder's one-track-per-rid
    dump is assembled from."""
    from paddle_tpu.profiler.profiler import _HostTracer
    tracer = _HostTracer()
    tracer.enabled = True
    tracer.add(name, start_ns, end_ns, event_type=event_type)
    ev = tracer.events[0]
    ev["tid"] = tid
    if args:
        ev["args"] = args
    return ev


class span:
    """``with span("name", **detail): ...`` or ``@span("name")``.

    A span object is one interval: make one per ``with`` (construction is
    two attribute stores).  The decorator form makes one per call."""

    __slots__ = ("name", "detail", "_ann")

    def __init__(self, name, **detail):
        self.name = name
        self.detail = detail
        self._ann = None

    def __enter__(self):
        self._ann = _trace_annotation()(self.name, **self.detail)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(*exc)
        return False

    def __call__(self, fn):
        name, detail = self.name, self.detail

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with span(name, **detail):
                return fn(*args, **kwargs)
        return wrapped
