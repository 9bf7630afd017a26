"""Continuous-batching serving engine on the ragged decode path.

The compiled decode step (models/llama_decode.py) already supports ragged
per-batch lengths and rewind, but a run-to-completion batch leaves finished
slots idling while the longest request drags the step.  This engine closes
that gap with Orca-style *iteration-level scheduling* — the technique behind
vLLM-class serving throughput — under the TPU constraint that every device
program keeps ONE static compiled shape:

* The device runs a fixed-batch-B step; a host-side scheduler retires
  finished slots (EOS / max-new-tokens) and admits queued requests into
  them *between* compiled steps.
* **Chunked prefill with budgeted interleaving** (``prefill_chunk``,
  default 256; ``prefill_budget`` chunks of prompt per scheduler step).
  Admission is INCREMENTAL: an admitted request enters a ``prefilling``
  state and its prompt is processed in whole ``P``-row chunks
  (``serving_prefill_chunk``) written straight into the slot's rows of
  the batch cache at a device-carried offset — short/tail chunks are
  length-masked, and each scheduler step spends at most
  ``prefill_budget`` chunks before dispatching the decode step, so a
  long prompt never stalls resident decode for its full prefill
  (Sarathi-style stall-free admission: the TPOT spike at admission is
  bounded by the budget).  The chunks a step spends on ONE prompt ride
  in ONE run of the program — ``[1, k * P]`` rows, ``k`` the widest
  power of two the budget and the prompt have left — so the weights are
  read once a step, not once a chunk; the program is generic in its row
  count, the power-of-two ladder (``_run_widths``) bounds the compiled
  programs at ``log2(prefill_budget) + 1`` for every prompt length, and
  the engine's first prefill run rehearses the other widths
  (``_rehearse_widths``), so nothing retraces or compiles after the
  first prefill-spending step.  The final run's program also returns the
  first sampled token — it stays device-resident and feeds the slot's
  first decode dispatch without a host round-trip; the host copy is
  synced at the next drain.  Chunked prefill is the only prefill: a
  request is admissible when its rows (prompt + max_new + headroom) fit
  ``max_len``.  Retired slots stay parked via
  ``ops.decode_attention.masked_lengths``: their write offset is lmax so
  every decode-step cache write DROPS — recycling needs no reshape,
  copy-out, or recompile.  The slot's first token is picked from the
  logit at its own last prompt column (pad columns are causally
  invisible to it).
* Decode runs either mode behind one ``ServingEngine.step()``: greedy
  (``sync_every`` tokens per dispatch via an inner lax.scan) or model-free
  prompt-lookup speculative drafting (serving_spec_step — the same
  _verify_and_emit verify/rewind machinery as the compiled while-loop, so
  speculation composes with mixed-length slots and emits exactly the
  verify forward's greedy picks; agreement with the 1-token-step program
  holds up to floating-point near-ties between the two program shapes).
* **Pipelined (double-buffered) dispatch**: step N+1 depends only on
  device-resident state — the carried ``cur`` tokens, caches, and
  lengths — so the engine dispatches it BEFORE syncing step N's tokens
  to the host.  Host-side emit/detokenize/stream-callback work and
  admission bookkeeping then overlap device compute; the drain-side
  block is measured by
  ``serving_pipeline_stall_seconds`` and the outstanding dispatch by the
  ``serving_inflight_steps`` gauge.  The ONE device→host sync per
  iteration goes through ``_host_fetch`` (the sanctioned sync point the
  tpu-lint PTL004 rule recognizes).  Correctness invariant: retirement
  and admission take effect ONE STEP LATE — a step dispatched before the
  scheduler discovers a slot finished still computes that slot, but the
  stale step is byte-harmless: ``masked_lengths`` gives a freed slot an
  offset of ``lmax`` at the NEXT dispatch so its writes drop, re-admission
  prefills are dispatched after the stale step in device program order so
  they overwrite its rows, rows past a new prompt's length are invisible
  to decode_attention's position masking, and the drain discards tokens
  whose slot no longer holds the same Request object.  The extra
  inflight dispatch is why ``_headroom`` is doubled.  A ``prefill_only``
  engine never decodes, so it has nothing to double-buffer: its step
  ends in the blocking first-token flush (``_flush_firsts``).

* **Paged KV cache** (``kv_block=``): the dense per-slot ``[B, Lmax]``
  cache rows become a global block pool indirected through per-slot
  block tables (serving/kv_cache.py has the allocator; the constructor
  docstring has the knob semantics).  Admission switches to total-live-
  token budgeting, identical prompt prefixes are adopted from a radix
  cache instead of re-prefilled, and refcount-0 cached blocks are
  evicted LRU-first under pressure — all host bookkeeping over the same
  compiled-program discipline (fixed shapes, zero retraces).

The per-slot state the scheduler owns host-side: token history, a length
mirror of the device cache, and the speculative rewind offset (folded into
the length mirror as ``+ j + 1`` per accepted round).  Decode-side cache
reads are length-adaptive: ``decode_chunk`` is forwarded to the chunked
online-softmax path in ops/decode_attention.py, so per-step HBM traffic
tracks the longest LIVE context instead of ``max_len``.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import threading
import time
import warnings
from collections import OrderedDict, deque

import numpy as np

import jax.numpy as jnp
from jax.errors import JaxRuntimeError

from paddle_tpu.models.llama_decode import _canon_weight_dtype
from paddle_tpu.models.serving_family import family_of
from paddle_tpu.observability.compilecache import phase as startup_phase
from paddle_tpu.observability.flightrecorder import (
    FlightRecorder, RequestTrace,
)
from paddle_tpu.observability.slo import SLOTracker
from paddle_tpu.observability.trace import span
from paddle_tpu.observability.watchdog import DeadlockWatchdog
from paddle_tpu.ops.decode_attention import _canon_kv_dtype, kv_rows_read
from paddle_tpu.serving.faults import InjectedDispatchError
from paddle_tpu.serving.kv_cache import (
    BlockStore, KVCacheManager, KVPoolExhausted, PagedKVCacheManager,
)
from paddle_tpu.serving.metrics import EngineMetrics

# the serving step/prefill programs donate their cache buffers (in-place
# update on TPU instead of a full-cache copy per dispatch); CPU has no
# donation support and warns per program — harmless here, silence it
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

__all__ = ["AcceptWindow", "EngineOverloaded", "KVPoolExhausted",
           "Request", "ServingEngine", "SpecConfig"]

# the ``policy`` label of every engine metric, recorder event and SLO
# gauge: iteration-level admission is the one scheduling policy
_POLICY = "continuous"

# the request's own transitions carry these details onto its timeline
_MARK_KEYS = ("slot", "chunk", "final")

_LOG = logging.getLogger(__name__)

# the transient device-error class the bounded dispatch retry targets
# (runtime/compile-service hiccups surface as XlaRuntimeError); the
# injected twin from serving/faults.py rides the same path so the retry
# machinery is provable without a flaky device
_RETRYABLE = (JaxRuntimeError, InjectedDispatchError)


class EngineOverloaded(RuntimeError):
    """``submit()`` rejected the request: the bounded admission queue
    (``max_pending``) is full.  Load shedding at the front door — the
    caller owns the backoff/reroute decision; the engine's resident work
    is never displaced."""


def _backoff_sleep(seconds):
    """The engine's sanctioned blocking wait: the exponential backoff
    between dispatch retry attempts.  Funneled through this one name for
    the same reason ``_host_fetch`` exists — the tpu-lint PTL008 rule
    keeps flagging raw ``time.sleep`` added inside step-dispatch loops
    without false-positiving on the bounded retry's deliberate backoff."""
    if seconds > 0:
        time.sleep(seconds)


def _host_fetch(*arrays):
    """The engine's sanctioned device→host sync point: materialize device
    arrays as numpy, blocking until their producing dispatches complete.
    Every OTHER engine/device interaction is an async dispatch — funneling
    the blocking reads through this one name is what lets the tpu-lint
    PTL004 rule keep flagging raw ``np.asarray`` added inside step loops
    without false-positiving on the pipelined drain."""
    return [np.asarray(a) for a in arrays]


# warn-once latch for the SpecConfig draft-model fallback (satellite
# contract: asking for model drafting without a model degrades to
# prompt-lookup LOUDLY, but only once per process — a fleet of workers
# constructing engines in a loop must not spam the log)
_SPEC_FALLBACK_WARNED = False


def _head_rows(routes, n):
    """The first ``n`` rows of a request's recorded routes (a list of
    ``[rows, L_moe, k]`` pieces, or None), as a list of pieces again."""
    if not n or routes is None:
        return None
    head = np.concatenate(routes, axis=0)[:n]
    if len(head) < n:
        raise RuntimeError(f"{len(head)} recorded rows for {n} reused ones")
    return [head]


def _warn_spec_fallback():
    global _SPEC_FALLBACK_WARNED
    if _SPEC_FALLBACK_WARNED:
        return
    _SPEC_FALLBACK_WARNED = True
    warnings.warn(
        "SpecConfig(source='draft_model') with no draft_model supplied — "
        "falling back to prompt-lookup drafting (this warning fires once "
        "per process)", RuntimeWarning, stacklevel=3)


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """THE validated speculative-decoding config: every drafting knob in
    one frozen value, checked loudly at construction instead of free-form
    kwargs failing deep inside the first compiled dispatch.

    ``source``: ``"prompt_lookup"`` (model-free n-gram mining from the
    slot's token history) or ``"draft_model"`` (a resident shrunk-llama
    draft model decoding ``spec_k`` candidates through its own compiled
    program).  ``draft_model``: the draft ``LlamaForCausalLM`` — required
    for model drafting; ``source="draft_model"`` WITHOUT one falls back
    to prompt-lookup with a once-per-process RuntimeWarning (the engine
    must keep serving when a deployment forgets to ship draft weights).
    ``spec_k``: draft tokens per verify round (``None`` inherits the
    engine's ``spec_k`` kwarg); under the adaptive policy this is the
    depth CEILING.  ``adaptive_window``: ``None`` = fixed k; an int >= 1
    sizes the per-slot sliding window of verify rounds whose accept rate
    drives the adaptive-k ladder (hard slots degrade toward ``k_min``
    instead of paying dead verify lanes).  ``k_min``: the adaptive
    floor.  ``tree``: ``None`` or ``"top2"`` — top-2 branching at the
    first draft position, verified in the same batched forward through a
    tree attention mask (draft-model source + dense caches only)."""

    source: str = "prompt_lookup"
    draft_model: object = None
    spec_k: object = None
    adaptive_window: object = None
    k_min: int = 1
    tree: object = None

    def __post_init__(self):
        if self.source not in ("prompt_lookup", "draft_model"):
            raise ValueError(
                f"SpecConfig: unknown source {self.source!r} — expected "
                "'prompt_lookup' or 'draft_model'")
        if self.spec_k is not None and (
                isinstance(self.spec_k, bool)
                or not isinstance(self.spec_k, int) or self.spec_k < 1):
            raise ValueError(
                f"SpecConfig: spec_k must be None (inherit the engine "
                f"knob) or an int >= 1, got {self.spec_k!r}")
        if self.adaptive_window is not None and (
                isinstance(self.adaptive_window, bool)
                or not isinstance(self.adaptive_window, int)
                or self.adaptive_window < 1):
            raise ValueError(
                f"SpecConfig: adaptive_window must be None (fixed k) or "
                f"an int >= 1 (verify rounds in the accept-rate window), "
                f"got {self.adaptive_window!r}")
        if isinstance(self.k_min, bool) or not isinstance(self.k_min, int) \
                or self.k_min < 1:
            raise ValueError(
                f"SpecConfig: k_min must be an int >= 1, got "
                f"{self.k_min!r}")
        if self.spec_k is not None and self.k_min > self.spec_k:
            raise ValueError(
                f"SpecConfig: k_min ({self.k_min}) exceeds spec_k "
                f"({self.spec_k})")
        if self.tree not in (None, "top2"):
            raise ValueError(
                f"SpecConfig: unknown tree {self.tree!r} — expected None "
                "(linear chain) or 'top2'")
        if self.tree is not None and self.source != "draft_model":
            raise ValueError(
                "SpecConfig: tree='top2' branches on the draft model's "
                "top-2 — it requires source='draft_model'")

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


class AcceptWindow:
    """Sliding window of (drafted, accepted) verify rounds — the accept
    rate that drives one slot's adaptive-k rung.  ``rate()`` is
    ``sum(accepted) / sum(drafted)`` over the last ``window`` rounds, or
    ``None`` while empty (a fresh slot holds its rung until evidence
    arrives).  Pure host arithmetic; one instance per slot."""

    def __init__(self, window):
        self.window = int(window)
        if self.window < 1:
            raise ValueError(
                f"AcceptWindow: window must be >= 1, got {window!r}")
        self._q = deque(maxlen=self.window)

    def push(self, drafted, accepted):
        if drafted < 0 or accepted < 0 or accepted > drafted:
            raise ValueError(
                f"AcceptWindow: need 0 <= accepted <= drafted, got "
                f"accepted={accepted} drafted={drafted}")
        self._q.append((int(drafted), int(accepted)))

    def rate(self):
        drafted = sum(d for d, _ in self._q)
        if not drafted:
            return None
        return sum(a for _, a in self._q) / drafted

    def reset(self):
        self._q.clear()

    def __len__(self):
        return len(self._q)


class Request:
    """One generation request.

    ``prompt_ids``: 1-D int token ids.  ``eos_token_id`` retires the slot
    when emitted (the EOS itself is kept in ``output_ids``).  ``stream_cb``
    (optional ``cb(request, new_ids)``) fires per emission batch — the
    streaming hook; with an engine ``detokenizer`` the accumulated text is
    kept current in ``.text``.  A raising ``stream_cb`` never kills the
    scheduler: the error is counted (``serving_stream_cb_errors_total``,
    labeled by exception type) and logged once per request, and decoding
    continues.  ``deadline_ms`` (optional) bounds submit -> completion:
    when it expires the request is retired wherever it is — queued,
    mid-prefill, or mid-decode — with whatever tokens it has.  Timing
    (perf_counter): ``t_submit`` / ``t_first`` (first token) /
    ``t_done``, with derived ``ttft`` / ``tpot`` / ``latency`` properties
    (None until available).

    ``status`` is the terminal-status state machine every front-end
    consumer reads: ``None`` while pending/in-flight, then exactly one of
    ``"done"`` (EOS / max_new_tokens), ``"timed_out"`` (deadline_ms),
    ``"cancelled"`` (host ``cancel()``/``close()``), ``"poisoned"``
    (non-finite logits quarantine) or ``"shed"`` (rejected at submit by
    the bounded admission queue).  ``done`` is True for every terminal
    status except ``"shed"`` (a shed request never entered the engine).

    ``slo_class`` names the request's traffic class for the engine's SLO
    tracker (observability/slo.py; ``None`` = the tracker's default,
    ``"interactive"``).  Classes must stay low-cardinality — they label
    the attainment/burn-rate gauges.  ``timeline()`` returns the
    engine-recorded lifecycle transitions (``queued`` → ``prefilling``
    per chunk → ``decoding`` → terminal status) as a list of ``{"t",
    "phase", ...}`` dicts on the ``perf_counter`` clock — empty until
    the request is submitted.

    ``priority`` (int, default 0, higher wins) orders admission and —
    on paged engines — arms preemption: when a strictly higher-priority
    request is queued and cannot be admitted, the engine parks the
    lowest-priority resident slot (its emitted tokens survive on the
    request; its KV chain survives EVICTABLE in the radix map) and
    re-queues it.  The resumed request re-adopts its own prefix, so a
    preemption round-trip costs one suffix prefill, not a recompute.
    ``preempts`` counts how many times this request was parked.
    All-default-priority traffic never preempts and admits in exact
    FIFO order — byte-identical to the pre-priority engine.
    """

    def __init__(self, prompt_ids, max_new_tokens, eos_token_id=None,
                 stream_cb=None, rid=None, deadline_ms=None,
                 slo_class=None, priority=0):
        self.prompt_ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        if self.prompt_ids.size == 0:
            raise ValueError("Request: empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("Request: max_new_tokens must be >= 1")
        self.eos_token_id = eos_token_id
        self.stream_cb = stream_cb
        self.rid = rid
        self.deadline_ms = (float(deadline_ms)
                            if deadline_ms is not None else None)
        if self.deadline_ms is not None and self.deadline_ms < 0:
            raise ValueError("Request: deadline_ms must be >= 0")
        self.slo_class = None if slo_class is None else str(slo_class)
        self.priority = int(priority)
        self.preempts = 0
        self._adm_ids = None      # tokens the last chunked admission prefilled
        self.output_ids = []
        # a model with routed experts: int8 [rows, L_moe, k] pieces, one row
        # a position the programs ran (prompt, then each emitted token's
        # input) — the experts that served it; None for any other model
        self.routes = None
        self.text = ""
        self.done = False
        self.status = None
        self.t_submit = None
        self.t_first = None
        self.t_done = None
        self._t_deadline = None   # stamped at submit()
        self._trace = None        # RequestTrace, attached at submit()
        self._cb_err_logged = False

    def timeline(self):
        """Lifecycle transitions the engine recorded for this request
        (class docstring); ``[]`` before ``submit()``."""
        tr = self._trace
        return [] if tr is None else tr.as_dicts()

    @property
    def latency(self):
        """submit -> completion seconds (None until done)."""
        if self.t_done is None or self.t_submit is None:
            return None
        return self.t_done - self.t_submit

    @property
    def ttft(self):
        """Time to first token: submit -> first emission seconds (None
        until the first token lands)."""
        if self.t_first is None or self.t_submit is None:
            return None
        return self.t_first - self.t_submit

    @property
    def tpot(self):
        """Time per output token AFTER the first: (t_done - t_first) /
        max(1, n_out - 1) seconds (None until done) — the steady-state
        decode rate, with the prefill-dominated first token excluded."""
        if self.t_done is None or self.t_first is None:
            return None
        return (self.t_done - self.t_first) / max(1, len(self.output_ids) - 1)


class _Phase:
    """What ``ServingEngine._phase`` returns: enters the span, and on exit
    stamps the phase's ``seconds`` on its flight-recorder event and feeds
    them to ``observe``."""

    __slots__ = ("_span", "_ev", "_observe", "_t0")

    def __init__(self, span_, ev, observe):
        self._span, self._ev, self._observe = span_, ev, observe

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        seconds = (time.perf_counter_ns() - self._t0) / 1e9
        if self._ev is not None:
            self._ev[5]["seconds"] = seconds
        if self._observe is not None:
            self._observe.observe(seconds)
        return False


def _under_init_phase(init):
    """``ServingEngine.__init__`` under the start-up record's
    ``serving.init`` phase (observability/compilecache.py).  The flight
    recorder does not exist when construction begins, so this one phase's
    event is written when it ends, with the ``seconds`` every phase's
    event carries."""
    @functools.wraps(init)
    def __init__(self, *args, **kwargs):
        with startup_phase("serving.init", step=-1) as ph:
            init(self, *args, **kwargs)
        self._event("init", seconds=ph.seconds)
    return __init__


class ServingEngine:
    """Fixed-batch continuous-batching engine over one causal LM.

    ``mode``: "greedy" or "spec" (model-free prompt-lookup speculative
    drafting, lossless — per-slot outputs byte-identical to greedy).
    ``sync_every``: greedy tokens decoded per host dispatch (inner scan);
    retirement/admission latency is bounded by it.
    ``detokenizer``: optional ``ids -> str`` for streamed ``.text``.
    ``decode_chunk``: KV chunk size for the length-adaptive cache read
    (ops/decode_attention.py); ``None`` reads the full ``[B, max_len]``
    cache every step.  The default (256) falls back to the full read
    automatically when ``max_len <= 256``.
    ``prefill_chunk``: prompt rows per chunk of the chunked prefill, the
    unit a prompt is spent in (clamped to ``max_len``).
    ``prefill_budget``: max chunks of prompt spent per scheduler step
    before the decode step goes out — bounds how long resident decode can
    stall on an admission.  The chunks a step spends on one prompt go out
    as one run of the prefill program (a power of two of them a run).
    ``kv_block``: paged KV cache — the per-layer cache becomes a global
    ``[num_blocks, kv_block, Hkv, D]`` pool indirected through per-slot
    block tables (serving/kv_cache.PagedKVCacheManager), with
    ``max_live_tokens`` (default ``batch_size * max_len``) sizing the
    pool: admission budgets total live TOKENS instead of slots, defers
    the queue head when the pool can't cover a request's worst case, and
    radix prefix hits adopt already-cached blocks so chunked prefill
    runs only the unmatched suffix.  Forces ``decode_chunk = kv_block``
    (the paged read IS the chunked loop).
    Token streams are byte-identical to the dense engine at f32
    (tested), and the block tables are traced operands — zero retraces
    across appends, prefix hits and evictions.
    ``host_tier_bytes`` / ``host_tier``: tiered KV cache — LRU eviction
    DEMOTES registered prefix chains into a byte-budgeted host-RAM
    ``BlockStore`` (a budget builds a private store; ``host_tier=``
    shares a caller-built one) instead of destroying them, and
    admission restores the host continuation of a prompt via a
    ``kv_transfer`` scatter (a device_put — cheaper than re-prefilling
    any prefix past ``host_tier_min_blocks`` blocks, the crossover
    knob).  Demotion copies are staged off the step path and
    materialized between scheduler steps; restores run at admission,
    never inside the dispatch loop; restored streams are byte-identical
    to never-evicted runs and the block tables still only change
    VALUES — zero retraces across a demote→restore wave.  Requires
    ``kv_block``.
    ``kv_dtype``: KV cache STORAGE dtype (``None`` = the model dtype).
    ``"int8"`` quantizes the cache — symmetric absmax over the head dim,
    one float16 scale per (position, head) row in a parallel pytree leaf
    riding the same donated-cache plumbing — quantized on append inside
    the cache scatter and dequantized inside the chunked attention read,
    so KV HBM traffic drops to ~0.53× of bf16 (~0.27× of f32).  Works
    with dense AND paged geometries (the scale pool shares the block
    tables — prefix reuse stays keyed on token ids) and with ``mesh``
    (scales head-sharded like the data).  Greedy streams can drift from
    the float engine within a small bounded rate (quantization error can
    flip near-tied argmaxes — the tested drift budget); every
    NON-quantized invariant (parking, poison quarantine, prefix
    adoption/accounting, paged-vs-dense and TP-vs-single-device parity
    WITHIN q8) stays byte-identical.
    ``mesh``: a ``jax.sharding.Mesh`` to tensor-parallel the compiled
    hot path across (``None`` = single-device, bitwise the pre-mesh
    engine).  Params are shard-placed once at construction under the
    llama TP rules and the KV cache shards along heads
    (serving/sharding.py); every host-facing operand stays replicated,
    so the scheduler, pipeline, and chunked prefill above this line run
    unchanged.  ``tp_axis`` names the mesh axis to shard along (default
    ``"mp"``); the attention and KV head counts must divide its size.

    Reliability layer (a strict no-op on the clean path — with no
    deadlines, no faults and ``max_pending=None`` the token streams,
    program identities and sync structure are unchanged):
    ``max_pending`` bounds the admission queue — a ``submit()`` that
    would push it past the bound raises ``EngineOverloaded`` (status
    ``"shed"``, counted in ``serving_requests_shed_total``) instead of
    growing an unbounded backlog.  ``retry_attempts`` /
    ``retry_backoff``: the decode dispatch and the drain-side fetch are
    wrapped in a bounded retry (exponential backoff through the
    sanctioned ``_backoff_sleep``) against transient
    ``XlaRuntimeError``-class failures; exhaustion re-raises.  Per-slot
    non-finite logits (the jitted ``ok`` flag riding every step's
    outputs through the SAME ``_host_fetch`` — no extra sync) quarantine
    the slot's request with status ``"poisoned"``; cohabiting slots are
    untouched (per-row attention isolation, tested byte-identical).
    ``faults``: a serving/faults.FaultPlan injecting deterministic
    dispatch errors / NaN payloads / slow steps / stream_cb crashes
    through test-only seams.  ``cancel(rid)`` and per-request
    ``deadline_ms`` retire work anywhere in its lifecycle via the same
    write-drop parking retirement the scheduler already uses — no
    recompile, no retrace.

    Request-lifecycle observability (host-side bookkeeping on the
    existing sync structure — zero new device syncs, and token outputs
    are byte-identical recorder-on vs recorder-off, tested):
    ``recorder`` is the always-on flight recorder — ``True`` (default)
    builds a :class:`~paddle_tpu.observability.flightrecorder.
    FlightRecorder` with defaults, ``False`` disables recording, or pass
    a configured instance (capacity / ``dump_dir`` for anomaly dumps).
    A ``timed_out``/``poisoned`` retirement or a retry exhaustion
    auto-dumps the last events and bumps
    ``flight_recorder_dumps_total{reason}``.  Every request also gets a
    rid-keyed lifecycle trace behind ``Request.timeline()``, aggregated
    into the ``serving_queue/prefill/decode_seconds`` phase histograms
    at retirement.  ``slo``: per-class SLO objectives — ``None`` uses
    :data:`~paddle_tpu.observability.slo.DEFAULT_OBJECTIVES`, or pass an
    iterable of ``SLObjective`` / a ready ``SLOTracker``; retirements
    feed the windowed ``serving_slo_attainment`` / ``_burn_rate``
    gauges by ``Request(slo_class=...)``.  ``debug_sources()`` plugs
    ``/debug/requests``, ``/debug/flightrecorder`` and ``/debug/slo``
    into a ``MetricsExporter``.
    """

    @_under_init_phase
    def __init__(self, model, batch_size=8, max_len=2048, mode="greedy",
                 spec_k=8, sync_every=1, detokenizer=None, registry=None,
                 instrument=True, decode_chunk=256, prefill_chunk=256,
                 prefill_budget=2, kv_block=None,
                 max_live_tokens=None, kv_dtype=None, mesh=None,
                 tp_axis="mp", max_pending=None, retry_attempts=3,
                 retry_backoff=0.05, faults=None, recorder=True,
                 slo=None, attn_impl=None, weight_dtype=None,
                 prefill_impl=None, tp_overlap=None,
                 prefill_only=False, on_prefilled=None, watchdog=None,
                 host_tier_bytes=None, host_tier=None,
                 host_tier_min_blocks=1, spec=None):
        if mode not in ("greedy", "spec"):
            raise ValueError(f"unknown mode {mode!r}")
        # ONE validated config for every drafting knob (SpecConfig): the
        # engine's legacy ``spec_k`` kwarg survives as the default depth,
        # everything else — draft source, draft model, adaptive window,
        # tree mode — routes through ``spec=``.  Asking for model
        # drafting without a model degrades to prompt-lookup with a
        # once-per-process warning; every other inconsistency is a loud
        # ValueError here, never a trace error inside the first dispatch.
        if spec is not None and mode != "spec":
            raise ValueError(
                "spec= carries speculative-drafting knobs — construct "
                f"the engine with mode='spec' (got mode={mode!r})")
        if mode == "spec":
            if spec is None:
                spec = SpecConfig()
            elif isinstance(spec, dict):
                spec = SpecConfig(**spec)
            elif not isinstance(spec, SpecConfig):
                raise ValueError(
                    f"spec= must be a SpecConfig or a kwargs dict, got "
                    f"{type(spec).__name__}")
            if spec.spec_k is None:
                spec = spec.replace(spec_k=int(spec_k))
            if spec.source == "draft_model" and spec.draft_model is None:
                _warn_spec_fallback()
                spec = spec.replace(source="prompt_lookup", tree=None)
            if spec.tree is not None and kv_block is not None:
                raise ValueError(
                    "spec tree='top2' requires dense caches (kv_block="
                    "None): the accepted-branch row repair scatters into "
                    "dense per-slot cache rows")
            spec_k = spec.spec_k
        else:
            spec = None
        self._spec = spec
        self._dspec = spec is not None and spec.source == "draft_model"
        # prefill/decode disaggregation seams (serving/disagg.py).  A
        # prefill-only engine owns admission + chunked prefill and NEVER
        # dispatches a decode program: every request carries max_new=1
        # (the first token is the prefill's own pick), each step's
        # blocking first-token flush retires the slot, and the paged
        # admission budget shrinks to the prompt's own blocks.
        # ``on_prefilled(request, slot, first)`` fires after the finite
        # check + radix registration and BEFORE the slot is released —
        # the window where the block chain is still mapped and
        # exportable.
        if prefill_only:
            if kv_block is None:
                raise ValueError(
                    "prefill_only requires paged KV (kv_block=): the "
                    "block chain is the migration transfer unit")
            if mode != "greedy":
                raise ValueError(
                    "prefill_only engines never decode — spec drafting "
                    "belongs to the decode worker")
        elif on_prefilled is not None:
            raise ValueError(
                "on_prefilled is the prefill_only completion hook — "
                "construct the engine with prefill_only=True")
        self._prefill_only = bool(prefill_only)
        self._on_prefilled = on_prefilled
        if mesh is not None and tp_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh has no axis {tp_axis!r} (axes: {mesh.axis_names})")
        mesh_devices = int(mesh.shape[tp_axis]) if mesh is not None else 1
        # observability: purely host-side counters/gauges/histograms/spans
        # (paddle_tpu/observability).  ``registry=None`` feeds the
        # process-wide registry; the benchmark passes a private one for
        # isolated readings.  ``instrument=False`` removes every metric
        # touch — token outputs are byte-identical either way (tested).
        self._m = (EngineMetrics(registry, _POLICY, int(batch_size),
                                  mesh_devices=mesh_devices)
                   if instrument else None)
        # request-scoped observability: the flight-recorder event ring,
        # rid-keyed lifecycle traces (Request.timeline() / /debug/requests)
        # and the sliding-window SLO tracker fed at retirement — all host
        # bookkeeping riding the existing drain, never a device value
        if recorder is True:
            recorder = FlightRecorder(policy=_POLICY)
        elif recorder is False:
            recorder = None
        self._fr = recorder
        if self._fr is not None and self._fr.on_dump is None \
                and self._m is not None:
            self._fr.on_dump = self._m.recorder_dump
        if isinstance(slo, SLOTracker):
            self._slo = slo
        else:
            self._slo = SLOTracker(
                objectives=slo, policy=_POLICY,
                registry=self._m.registry if self._m is not None else None)
        # the scheduler-step index: -1 until the first step, which is what
        # construction's own phases carry
        self._step_idx = -1
        self._traces = OrderedDict()   # rid -> RequestTrace, newest last
        self._trace_cap = 1024
        self._trace_lock = threading.Lock()
        # runtime deadlock watchdog (observability/watchdog.py):
        # ``watchdog=<seconds>`` arms a daemon thread that dumps every
        # thread's stack through the flight recorder when the step loop
        # goes stale past the threshold WITH work outstanding.  The
        # probe reads `_last_step_unix` (stamped 0 until the first
        # step), so it stays quiet through construction and idle.
        self._last_step_unix = 0.0
        self._watchdog = None
        if watchdog:
            self._watchdog = DeadlockWatchdog(
                self._watchdog_probe, stall_after=float(watchdog),
                recorder=self._fr,
                registry=self._m.registry if self._m is not None else None,
                component=_POLICY).start()
        self._B = int(batch_size)
        self._lmax = int(max_len)
        self._mode = mode
        self._spec_k = int(spec_k)
        self._sync = max(1, int(sync_every))
        self._detok = detokenizer
        self._chunk = int(decode_chunk) if decode_chunk else None
        if not prefill_chunk or int(prefill_chunk) < 1:
            raise ValueError(
                f"prefill_chunk must be an int >= 1, got {prefill_chunk!r} "
                "(chunked prefill is the only prefill)")
        # a chunk wider than the cache would only pad — clamp so small
        # max_len engines don't pay a [1, 256] forward per tiny prompt
        self._pchunk = min(int(prefill_chunk), self._lmax)
        self._pbudget = max(1, int(prefill_budget))
        # paged KV geometry: ``kv_block`` switches the cache to a global
        # block pool + per-slot block tables with radix prefix reuse, and
        # admission to total-live-TOKEN budgeting (``max_live_tokens``).
        # The paged read IS the chunked attention loop (one gather per
        # chunk), so decode_chunk is forced to the block size, and the
        # block/chunk sizes must divide one another so a prefix hit's
        # suffix chunks start on the same chunk boundaries a miss would
        # prefill — the byte-identity condition across hit/miss admission.
        self._paged = kv_block is not None
        if self._paged:
            kv_block = int(kv_block)
            if self._pchunk % kv_block and kv_block % self._pchunk:
                raise ValueError(
                    f"prefill_chunk ({self._pchunk}) and kv_block "
                    f"({kv_block}) must divide one another (prefix hits "
                    "must land on prefill-chunk boundaries)")
            self._chunk = kv_block
        elif max_live_tokens is not None:
            raise ValueError("max_live_tokens requires kv_block (paged KV)")
        # the model seam (models/serving_family.py): everything the engine
        # knows of the architecture — its weights pytree, its compiled
        # programs, its cache leaves, its partition rules — is read off
        # this one record; options the family cannot serve raise here
        fam = self._fam = family_of(model)
        with self._init_phase("init.params"):
            self._params, self._cfg = fam.decode_params(model, self._lmax)
        rows = fam.rows_leaves(self._cfg)
        nh, (nkv, hd) = rows.query_heads, rows.row
        fam.check_options(dict(
            cfg=self._cfg, mode=mode, kv_block=kv_block, mesh=mesh,
            kv_dtype=kv_dtype, weight_dtype=weight_dtype,
            attn_impl=attn_impl, prefill_impl=prefill_impl,
            tp_overlap=tp_overlap, prefill_chunk=self._pchunk))
        # kv_dtype: cache STORAGE dtype override.  None keeps the model
        # dtype (bitwise the pre-quantization engine — kv_dtype simply
        # never enters the program identity as a non-None static).
        # "int8" switches every cache leaf to a quantized (data, scale)
        # pair: quantize-on-append, dequant inside the chunked read
        # (ops/decode_attention.py) — ~0.53× the KV bytes of bf16.
        # Validated against the supported set here, at construction, so a
        # typo fails loudly instead of deep inside the first dispatch.
        self._kv_dtype = (_canon_kv_dtype(kv_dtype, "ServingEngine")
                          if kv_dtype is not None else None)
        self._q8 = self._kv_dtype == "int8"
        self._kvq = "int8" if self._q8 else "off"
        # attn_impl: cache-READ implementation.  None/"reference" keeps the
        # chunked lax.while_loop (bitwise the pre-kernel engine — like
        # kv_dtype=None it never enters the program identity as non-None);
        # "pallas" routes decode_attention through the fused Pallas kernel
        # (ops/paged_attention_pallas.py) — gather + dequant + online
        # softmax in one VMEM residency, interpret mode off-TPU.
        if attn_impl not in (None, "reference", "pallas"):
            raise ValueError(
                f"ServingEngine: unknown attn_impl {attn_impl!r} — "
                "supported: None (reference), 'reference', 'pallas' "
                "(fused kernel, falls back per-call when the geometry "
                "is unsupported)")
        self._attn_impl = attn_impl
        self._attn_label = "fused" if attn_impl == "pallas" else "reference"
        # prefill_impl: chunked-prefill implementation.  None/"reference"
        # keeps the dense fold + scatter append; "pallas" fuses the
        # causal-masked chunk attention WITH the (quantize-on-)append into
        # one kernel (ops/prefill_attention_pallas.py), falling back
        # per-call when the chunk geometry is unsupported.
        if prefill_impl not in (None, "reference", "pallas"):
            raise ValueError(
                f"ServingEngine: unknown prefill_impl {prefill_impl!r} — "
                "supported: None (reference), 'reference', 'pallas' "
                "(fused prefill+append kernel, falls back per-call when "
                "the chunk geometry is unsupported)")
        self._prefill_impl = prefill_impl
        self._prefill_label = ("fused" if prefill_impl == "pallas"
                               else "reference")
        # tp_overlap: split the row-parallel projections (wo/down) into N
        # output-feature segments so each segment's psum can overlap the
        # next segment's matmul.  None/0 keeps the single fused matmul;
        # int >= 2 is the segment count (byte-identical outputs — the
        # per-element dot products are unchanged, only issue order moves).
        if tp_overlap is not None:
            if isinstance(tp_overlap, bool) or not isinstance(
                    tp_overlap, int) or tp_overlap < 2:
                raise ValueError(
                    f"ServingEngine: tp_overlap must be None or an int "
                    f">= 2 (segment count), got {tp_overlap!r}")
        self._tp_overlap = tp_overlap
        # weight_dtype: decode matmul WEIGHT storage.  "int8" swaps the
        # seven projection weights for symmetric per-output-channel
        # quantized copies with f16 scales (quantize_decode_weights) —
        # dequant-in-matmul keeps the host-facing API unchanged.
        self._weight_dtype = _canon_weight_dtype(weight_dtype,
                                                 "ServingEngine")
        self._w8 = self._weight_dtype == "int8"
        self._wq_label = "int8" if self._w8 else "off"
        if self._w8:
            if fam.quantize_weights is None:
                raise ValueError(
                    f"ServingEngine: the {fam.name} family has no int8 "
                    "weight quantizer (weight_dtype=)")
            # quantize AFTER the model cache handed us its pytree (a fresh
            # dict — the cache entry itself is never mutated) and BEFORE
            # any mesh placement so the int8 leaves shard directly
            self._params = fam.quantize_weights(
                self._params, self._weight_dtype)
        # resident draft model (SpecConfig source="draft_model"): its
        # decode pytree lives alongside the target's and rides the same
        # weight-quantization / mesh-placement path.  Paged engines share
        # ONE block pool across both tenants — draft layer l reads/writes
        # target layer l's pool arrays through its own block tables — so
        # the geometries that alias (kv heads, head dim, dtype, layer
        # count <= target's) are validated here, loudly.
        self._dparams = self._dcfg = None
        self._dcaches = None
        if self._dspec:
            if family_of(spec.draft_model) is not fam \
                    or fam.spec_draft_step is None:
                raise ValueError(
                    "SpecConfig(source='draft_model'): the draft model "
                    f"must be of the target's serving family ({fam.name}) "
                    "and the family must have a draft-step program")
            self._dparams, self._dcfg = fam.decode_params(
                spec.draft_model, self._lmax)
            drows = fam.rows_leaves(self._dcfg)
            dnh, (dnkv, dhd) = drows.query_heads, drows.row
            if int(self._dparams["embed"].shape[0]) \
                    != int(self._params["embed"].shape[0]):
                raise ValueError(
                    f"draft model vocab "
                    f"{int(self._dparams['embed'].shape[0])} != target "
                    f"vocab {int(self._params['embed'].shape[0])} — the "
                    "verify forward compares token ids, so the vocabs "
                    "must match")
            if self._paged:
                if len(self._dparams["layers"]) > len(
                        self._params["layers"]):
                    raise ValueError(
                        f"paged draft sharing: draft layer count "
                        f"{len(self._dparams['layers'])} exceeds target "
                        f"{len(self._params['layers'])} (draft layer l "
                        "rides target layer l's pool array)")
                if (dnkv, dhd) != (nkv, hd):
                    raise ValueError(
                        f"paged draft sharing: draft KV geometry "
                        f"(kv_heads={dnkv}, head_dim={dhd}) != target "
                        f"({nkv}, {hd}) — blocks are model-agnostic "
                        "bytes only when the per-row shapes match; use "
                        "a dense engine for mismatched drafters")
                if self._dparams["embed"].dtype \
                        != self._params["embed"].dtype:
                    raise ValueError(
                        f"paged draft sharing: draft dtype "
                        f"{self._dparams['embed'].dtype} != target "
                        f"{self._params['embed'].dtype}")
            if self._w8:
                self._dparams = fam.quantize_weights(
                    self._dparams, self._weight_dtype)
        # the declarative program identity: every static kernel/precision
        # knob flows through this ONE frozen registry value — the four
        # serving impls, the TP program cache and the jit static axes all
        # consume it instead of hand-threaded per-impl keyword lists
        # (serving/program_key.py re-validates each axis on construction)
        from paddle_tpu.serving.program_key import ProgramKey
        self._pk = ProgramKey(
            attn_impl=self._attn_impl, prefill_impl=self._prefill_impl,
            kv_dtype=self._kv_dtype, weight_dtype=self._weight_dtype,
            tp_overlap=self._tp_overlap,
            draft_source=spec.source if spec is not None else None,
            spec_depth=self._spec_k if spec is not None else None,
            spec_tree=spec.tree if spec is not None else None)
        # adaptive draft length: per-slot AcceptWindows drive a rung on a
        # power-of-two ladder [k_min .. spec_k]; the batch runs ONE
        # program per round at min(live slots' rungs), moving one rung
        # per round (each depth is its own compiled program — the ladder
        # is what bounds how many the warm set holds)
        if spec is not None and spec.adaptive_window is not None:
            rungs = {spec.k_min, self._spec_k}
            r = 1
            while r < self._spec_k:
                if r > spec.k_min:
                    rungs.add(r)
                r *= 2
            self._k_rungs = sorted(rungs)
            self._awin = [AcceptWindow(spec.adaptive_window)
                          for _ in range(self._B)]
        else:
            self._k_rungs = [self._spec_k]
            self._awin = None
        self._k_cur = self._k_rungs[-1]
        self._k_want = [len(self._k_rungs) - 1] * self._B
        dtype = (self._kv_dtype if self._kv_dtype is not None
                 else self._params["embed"].dtype)
        # mesh=None: single-device engine, module-level jitted programs,
        # byte-identical to every prior release.  mesh set: params are
        # shard-placed ONCE here under the llama TP rules, the KV cache is
        # head-sharded, and the four entry points dispatch through the
        # process-wide cached TP programs (serving/sharding.py).  Host
        # scheduler state (cur/lengths/queues) stays replicated either way.
        self._tp = None
        self._tp_spec = None   # adaptive-k ladder: {rung k: TPPrograms}
        cache_sharding = None
        scale_sharding = None
        if mesh is not None:
            from paddle_tpu.serving.sharding import (
                shard_decode_params, serving_tp_programs)
            n = mesh_devices
            if fam.tp_rules is None:
                raise ValueError(
                    f"ServingEngine: the {fam.name} family has no "
                    "tensor-parallel rule set (mesh=)")
            if nkv % n or nh % n:
                raise ValueError(
                    f"heads not shardable {n}-way along {tp_axis!r}: "
                    f"num_attention_heads={nh}, num_key_value_heads={nkv} "
                    f"(the KV cache shards along heads)")
            with self._init_phase("init.params"):
                self._params, pspecs = shard_decode_params(
                    self._params, mesh, axis=tp_axis,
                    rules=fam.tp_rules(tp_axis))
                dspecs = None
                if self._dspec:
                    if dnkv % n or dnh % n:
                        raise ValueError(
                            f"draft heads not shardable {n}-way along "
                            f"{tp_axis!r}: num_attention_heads={dnh}, "
                            f"num_key_value_heads={dnkv} (the draft KV "
                            "shards along heads like the target's)")
                    self._dparams, dspecs = shard_decode_params(
                        self._dparams, mesh, axis=tp_axis,
                        rules=fam.tp_rules(tp_axis))
            d_layers = (len(self._dparams["layers"]) if self._dspec
                        else 0)
            self._tp = serving_tp_programs(
                mesh, tp_axis, self._cfg, pspecs,
                len(self._params["layers"]), sync_every=self._sync,
                spec_k=self._spec_k, with_hist=mode == "spec",
                chunk_size=self._chunk, paged=self._paged,
                program_key=self._pk, dcfg=self._dcfg,
                dparam_specs=dspecs, d_layers=d_layers)
            if mode == "spec":
                # one compiled spec program per ladder rung (a depth IS
                # a program shape); the top rung is the base TPPrograms
                self._tp_spec = {self._spec_k: self._tp}
                for k in self._k_rungs[:-1]:
                    self._tp_spec[k] = serving_tp_programs(
                        mesh, tp_axis, self._cfg, pspecs,
                        len(self._params["layers"]),
                        sync_every=self._sync, spec_k=k,
                        with_hist=True, chunk_size=self._chunk,
                        paged=self._paged,
                        program_key=self._pk.replace(spec_depth=k),
                        dcfg=self._dcfg, dparam_specs=dspecs,
                        d_layers=d_layers)
            cache_sharding = self._tp.cache_sharding
            scale_sharding = self._tp.scale_sharding
        # host KV tier: evictions demote into a byte-budgeted host-RAM
        # BlockStore and admission restores from it (a device_put, not a
        # suffix prefill).  ``host_tier=`` shares a caller-built store;
        # ``host_tier_bytes=`` builds a private one.
        if host_tier is not None and host_tier_bytes is not None:
            raise ValueError(
                "pass host_tier= (a BlockStore) OR host_tier_bytes= (a "
                "budget for a private one), not both")
        host_store = host_tier
        if host_store is None and host_tier_bytes:
            if not self._paged:
                raise ValueError(
                    "host_tier_bytes requires paged KV (kv_block=): only "
                    "a block pool has demotable prefix chains")
            host_store = BlockStore(int(host_tier_bytes), block=kv_block)
        if host_store is not None and not self._paged:
            raise ValueError(
                "host_tier requires paged KV (kv_block=): only a block "
                "pool has demotable prefix chains")
        self._host_min_blocks = max(1, int(host_tier_min_blocks))
        with self._init_phase("init.cache"):
            if self._paged:
                # a resident draft model is a second pool tenant: its chains
                # grow in lockstep with the target's, so the default pool
                # doubles (an explicit max_live_tokens is the caller's
                # sizing decision and is respected as-is)
                self._kv = PagedKVCacheManager(
                    len(self._params["layers"]), self._B, self._lmax, nkv, hd,
                    dtype, block=kv_block,
                    max_live_tokens=(int(max_live_tokens) if max_live_tokens
                                     else (2 if self._dspec else 1)
                                     * self._B * self._lmax),
                    sharding=cache_sharding, on_event=self._kv_event,
                    scale_sharding=scale_sharding, host_store=host_store)
            else:
                self._kv = KVCacheManager(
                    len(self._params["layers"]), self._B, self._lmax, nkv, hd,
                    dtype, sharding=cache_sharding,
                    scale_sharding=scale_sharding,
                    init_layer=lambda: fam.init_layer_cache(
                        self._cfg, self._B, self._lmax, dtype))
                if self._dspec:
                    # dense draft tenancy: a SEPARATE per-draft-layer cache
                    # list (dense rows are slot-indexed — cohabitation in the
                    # target's arrays would clobber it), same storage dtype
                    # rules and head sharding as the target's
                    from paddle_tpu.serving.kv_cache import _place_caches
                    ddtype = (self._kv_dtype if self._kv_dtype is not None
                              else self._dparams["embed"].dtype)
                    self._dcaches = [
                        fam.init_layer_cache(self._dcfg, self._B, self._lmax,
                                             ddtype)
                        for _ in range(len(self._dparams["layers"]))]
                    if cache_sharding is not None:
                        self._dcaches = _place_caches(
                            self._dcaches, cache_sharding, scale_sharding)
        # a family with routed experts hands back, beside the tokens of a
        # dispatch and of a prefill chunk, the experts that served each
        # live row: drained with the tokens, counted, and appended to
        # Request.routes.  ``_chunk_routes``: (request, real rows, device
        # array, the request's ``preempts`` then) of the prefill runs
        # dispatched since the last decode dispatch, whose record they ride
        self._n_experts = (fam.routed_experts(self._params)
                           if fam.routed_experts is not None else 0)
        self._chunk_routes = []
        # recurrent state beside the K/V rows (a family's state_leaves):
        # resident bytes for the serving_state_bytes gauge
        self._state_idx = tuple(leaf.index for leaf in fam.state_leaves)
        if self._m is not None:
            self._m.state_bytes.set(sum(
                int(layer[i].nbytes) for layer in self._kv.caches
                for i in self._state_idx))
            self._m.set_kv_quant(self._kvq)
            self._m.set_decode_kernel(self._attn_label)
            self._m.set_prefill_kernel(self._prefill_label)
            self._m.set_tp_overlap(self._tp_overlap or 0)
            self._m.set_weight_quant(self._wq_label)
            if spec is not None:
                self._m.set_spec_source(spec.source)
                self._m.spec_draft_k.set(self._spec_k)
            if self._q8:
                # analytic per-context-token KV traffic at int8: 1 data
                # byte per (head, dim) element + 2 f16 scale bytes per
                # (position, head) row, every rows leaf (k and v), every
                # layer
                n_layers = len(self._params["layers"])
                self._m.hbm_gb_per_tok_q8.set(
                    n_layers * rows.count * nkv * (hd + 2) / 1e9)
            if self._w8:
                # analytic per-decode-token WEIGHT traffic at int8: every
                # projection element is read once per token — 1 byte of
                # data plus 2 f16 scale bytes per output channel (global
                # .size, placement-independent)
                wbytes = sum(
                    lp[n].size + 2 * lp[n + "_scale"].size
                    for lp in self._params["layers"]
                    for n in ("wq", "wk", "wv", "wo", "gate", "up", "down"))
                self._m.hbm_gb_per_tok_w8.set(wbytes / 1e9)
        # paged decode-time row growth is capped per slot by the token
        # budget reserved at admission (prompt + max_new + headroom,
        # clamped to lmax) — the mirror _spend/_dispatch draw ensure_rows
        # against
        self._need_rows = np.zeros((self._B,), np.int64)
        # host mirror of the carried next-token per slot; lengths and the
        # slot -> request table live on the cache manager.  Handed to a
        # dispatch as jnp.asarray(self._cur.copy()) — a copy: the mirror is written
        # in place while dispatches are in flight, and the CPU backend may
        # alias a numpy operand zero-copy (kv_cache.device_lengths)
        self._cur = np.zeros((self._B,), np.int32)
        if mode == "spec":
            self._hist = jnp.zeros((self._B, self._lmax), jnp.int32)
            self._hist_len = jnp.zeros((self._B,), jnp.int32)
        else:
            self._hist = self._hist_len = None
        self._queue = deque()
        self._finished = []
        self._next_rid = 0
        self._rids = set()
        # pipelined-dispatch state: the one outstanding (dispatched, not yet
        # drained) step, the device-resident carries feeding the NEXT
        # dispatch without a host round-trip, and the slots adopted
        # (``adopt_prefilled``) since the last dispatch, whose cur/length
        # live host-side until mixed in
        self._inflight = None
        self._dev_cur = None
        self._dev_len = None
        self._adm_pending = set()
        # chunked-prefill state: per-slot prefill progress (insertion order
        # = admission order, the budget-spend order), the device-resident
        # first token of slots whose final chunk is dispatched but whose
        # host copy has not been drained yet, the (slot, request, first)
        # triples awaiting host emission; the widths (in chunks) a prefill
        # run may take, and whether each has been compiled (the first run
        # rehearses the others)
        self._pf = {}
        self._dev_first = {}
        self._pending_firsts = []
        self._widths = self._run_widths()
        self._widths_warm = len(self._widths) == 1
        self._t_lastdrain = None
        # reliability state: the bounded admission queue, the dispatch
        # retry policy, the fault-injection plan (None in production) and
        # the scheduler-step index the plan keys its injections to
        self._max_pending = (int(max_pending)
                             if max_pending is not None else None)
        if self._max_pending is not None and self._max_pending < 0:
            raise ValueError("max_pending must be >= 0 or None")
        self._retry_attempts = max(1, int(retry_attempts))
        self._retry_backoff = float(retry_backoff)
        self._faults = faults
        # fleet-facing host counters, maintained UNCONDITIONALLY (a
        # router reads them through stats() even on instrument=False
        # engines): paged prompt/reuse token totals (the fleet hit-rate
        # ratio) and the preemption park/resume tallies
        self._n_prompt_tokens = 0
        self._n_reuse_tokens = 0
        self._n_preempted = 0
        self._n_resume_suffix = 0
        self._n_resume_total = 0
        self._n_host_reuse_tokens = 0

    # ------------------------------------------------------------- scheduling
    @property
    def has_work(self):
        return (bool(self._queue) or self._kv.any_live()
                or self._inflight is not None)

    def _watchdog_probe(self):
        """Watchdog progress probe: last step time while work is
        outstanding, None when idle (an idle engine is not stalled)."""
        t = self._last_step_unix
        if not t or not self.has_work:
            return None
        return t

    def _headroom(self):
        # greedy may overshoot a retiring slot by < sync_every cache rows;
        # spec's verify forward writes spec_k+1 rows before the rewind
        # (+1 more under tree mode: the branch token appends at L+k+1)
        if self._mode == "spec":
            per = self._spec_k + (
                2 if self._spec is not None and self._spec.tree else 1)
        else:
            per = self._sync
        # a decoding engine discovers retirement one drain late, so one
        # extra full dispatch of cache writes can land past the emission
        # point before the slot's offset is masked to lmax
        return per if self._prefill_only else 2 * per

    def submit(self, request):
        if self._prefill_only and request.max_new_tokens != 1:
            raise ValueError(
                "prefill-only engine: requests carry max_new_tokens=1 "
                "(the prefill's own first token) — decode belongs to a "
                f"decode worker, got max_new={request.max_new_tokens}")
        p = int(request.prompt_ids.size)
        need = p + request.max_new_tokens + self._headroom()
        if need > self._lmax:
            raise ValueError(
                f"request needs {need} cache rows (prompt {p} + "
                f"max_new {request.max_new_tokens} + headroom "
                f"{self._headroom()}) > max_len {self._lmax}")
        # load shedding AFTER validation (a malformed request stays a
        # ValueError) but BEFORE rid assignment (a shed request never
        # consumes engine state): bounding what's QUEUED — resident slots
        # are capacity already paid for — keeps worst-case queue wait
        # proportional to max_pending, the backpressure contract
        if self._max_pending is not None \
                and len(self._queue) >= self._max_pending:
            request.status = "shed"
            if self._m is not None:
                self._m.terminal("shed")
            self._event("shed", request, queued=len(self._queue))
            raise EngineOverloaded(
                f"admission queue full ({len(self._queue)} pending >= "
                f"max_pending={self._max_pending}); request shed")
        if request.rid is None:
            # the engine assigns (and only then advances) the auto rid
            request.rid = self._next_rid
            self._next_rid += 1
        else:
            # a caller-provided rid must never collide with one already
            # handed out, nor silently alias a FUTURE auto rid: reject the
            # former, bump the auto counter past the latter
            if request.rid in self._rids:
                raise ValueError(
                    f"rid {request.rid!r} is already in use by another "
                    "request on this engine")
            if isinstance(request.rid, int):
                self._next_rid = max(self._next_rid, request.rid + 1)
        self._rids.add(request.rid)
        request.t_submit = time.perf_counter()
        if request.deadline_ms is not None:
            request._t_deadline = request.t_submit \
                + request.deadline_ms / 1e3
        self._attach_trace(request)
        # lifecycle trace: born "queued"
        with self._phase("submit", request, mark="queued", prompt_len=p,
                         slo_class=request.slo_class):
            self._queue.append(request)
            if self._m is not None:
                self._m.queue_depth.set(len(self._queue))
        return request

    def _attach_trace(self, request):
        """Give ``request`` its lifecycle trace, kept in a bounded
        rid-keyed index so /debug/requests can show recent timelines
        without unbounded growth (the Request itself keeps its own trace
        alive regardless).  recorder=False switches off ALL
        request-scoped recording — timelines included."""
        if self._fr is None:
            return
        tr = RequestTrace(request.rid)
        request._trace = tr
        with self._trace_lock:
            self._traces[request.rid] = tr
            while len(self._traces) > self._trace_cap:
                self._traces.popitem(last=False)

    def _decodable(self, i):
        """Slot ``i`` holds a live request that finished prefilling — the
        population the decode dispatch runs over.  Slots mid-prefill stay
        parked (masked_lengths) until their final chunk is dispatched."""
        return self._kv.reqs[i] is not None and i not in self._pf

    # --------------------------------------------------- priority preemption
    @staticmethod
    def _admission_ids(r):
        """The token sequence a (re-)admission must prefill: the prompt,
        plus — for a request resuming after preemption — every token it
        already emitted.  The emitted tokens' KV rows must exist before
        decode continues, and the LAST emitted token's forward is exactly
        what produces the next one, so re-admitting this sequence through
        the ordinary chunked-prefill path continues the greedy stream
        byte-identically."""
        if not r.output_ids:
            return r.prompt_ids
        return np.concatenate(
            [r.prompt_ids, np.asarray(r.output_ids, np.int32)])

    def _preempt_slot(self, slot):
        """Park ``slot``'s request mid-decode.  The tokens whose KV rows
        are verified written — the prompt plus every emitted token but
        the last (the last token's row is written by the NEXT dispatch,
        which the park cancels) — are registered into the radix map, so
        ``release`` parks that chain EVICTABLE instead of freeing it and
        the resume admission re-adopts it for the cost of one suffix
        prefill.  An inflight pipelined dispatch for this slot is
        harmless by the same one-step-late invariant retirement rides:
        its writes land only in blocks PAST the registered chain (freed,
        and overwritten in device program order if reallocated) and its
        drained tokens fail the request-identity check."""
        r = self._kv.reqs[slot]
        cached = self._admission_ids(r)[:-1]
        self._kv.register_prefix(slot, cached)
        self._kv.release(slot)
        self._forget_slot(slot)
        r.preempts += 1
        r._adm_ids = None
        self._n_preempted += 1
        self._event("preempt", r, mark="preempted", slot=slot,
                    cached_tokens=int(cached.size), n_out=len(r.output_ids))
        self._queue.appendleft(r)
        if self._m is not None:
            self._m.preempted.inc()
            self._m.queue_depth.set(len(self._queue))
            self._m.slots_occupied.set(self._kv.occupied())
            self._m.live_tokens.set(self._kv.live_tokens())

    def _maybe_preempt(self):
        """Park low-priority resident work when a strictly higher-priority
        waiter is blocked (no free slot, or the block pool cannot cover
        its worst case).  Victims go lowest priority first; within a
        class the most recently submitted loses (old work keeps
        finishing).  Paged engines only — and a strict no-op while every
        queued priority <= every resident priority, which is what keeps
        all-default traffic byte-identical."""
        if not self._paged or not self._queue:
            return
        top = max(self._queue, key=lambda q: q.priority)
        for _ in range(self._B):
            victims = [
                (i, self._kv.reqs[i]) for i in range(self._B)
                if self._kv.reqs[i] is not None and i not in self._pf
                and self._kv.reqs[i].t_first is not None
                and self._kv.reqs[i].priority < top.priority]
            if not victims:
                return
            # is the head actually blocked?  mirror the admission math
            # (worst-case rows minus the radix match, chunk-aligned)
            tok = self._admission_ids(top)
            C, P = self._kv.block, self._pchunk
            p = int(tok.size)
            rem = max(1, top.max_new_tokens - len(top.output_ids))
            need = min(self._lmax, p + rem + self._headroom())
            off0, shared = self._kv.match_prefix(tok)
            if P > C:
                off0 = (off0 // P) * P
                shared = shared[:off0 // C]
            budget = -(-need // C) - len(shared)
            if self._kv.free_slots() and self._kv.can_reserve(budget):
                return   # admissible as-is — nothing to displace
            slot, _ = min(victims,
                          key=lambda sr: (sr[1].priority, -sr[1].t_submit))
            self._preempt_slot(slot)

    # -------------------------------------------------- request lifecycle
    # terminal statuses beyond "done": every path below retires through
    # the SAME write-drop parking the scheduler already uses (the slot's
    # masked offset goes to lmax at the next dispatch, its stale pipelined
    # tokens fail the request-identity drain check) — no recompile, no
    # retrace, and the freed slot re-admits immediately.

    def _on_terminal(self, r, status, slot=None):
        """Request-scoped observability fanout, once per terminal
        transition: the timeline's terminal mark, the flight-recorder
        ``retire`` event, the lifecycle phase histograms and the SLO
        window — plus the anomaly auto-dump for ``timed_out`` /
        ``poisoned`` (retry exhaustion dumps from ``_retry``).  Pure host
        bookkeeping; the scheduling state machine is untouched."""
        tr = r._trace
        self._event("retire", r, mark=status, slot=slot, status=status,
                    n_out=len(r.output_ids))
        if self._fr is not None and status in ("timed_out", "poisoned"):
            self._fr.auto_dump(status)
        if self._m is not None and tr is not None:
            self._m.observe_phases(tr.durations())
        if self._slo is not None:
            self._slo.observe(r)

    def _terminal_queued(self, r, status):
        """Retire a request that never reached a slot (still queued)."""
        r.status = status
        r.done = True
        r.t_done = time.perf_counter()
        self._finished.append(r)
        if self._m is not None:
            self._m.terminal(status)
        self._on_terminal(r, status)

    def _forget_slot(self, slot):
        """Drop every piece of per-slot scheduler state that outlives the
        slot's request: chunked-prefill progress, the device-resident
        first token, just-adopted membership and not-yet-drained
        first-token records.  Records already riding an inflight dispatch
        need no scrub — the drain's identity check discards them."""
        st = self._pf.pop(slot, None)
        if st is not None and self._chunk_routes:
            # chunks of the interrupted prefill that no dispatch has taken
            self._chunk_routes = [c for c in self._chunk_routes
                                  if c[0] is not st["req"]]
        self._dev_first.pop(slot, None)
        self._adm_pending.discard(slot)
        self._pending_firsts = [t for t in self._pending_firsts
                                if t[0] != slot]

    def _retire(self, slot, status):
        """Retire ``slot``'s request with a non-``done`` terminal status
        (timed_out / cancelled / poisoned), keeping whatever tokens it
        already emitted as its partial output."""
        r = self._kv.reqs[slot]
        r.status = status
        r.done = True
        r.t_done = time.perf_counter()
        self._kv.release(slot)
        self._forget_slot(slot)
        self._finished.append(r)
        if self._m is not None:
            self._m.terminal(status)
            self._m.slots_occupied.set(self._kv.occupied())
        self._on_terminal(r, status, slot=slot)

    def cancel(self, rid):
        """Host-side cancellation: retire ``rid`` wherever it is —
        queued, mid-prefill (``_pf``) or mid-decode-flight (stale
        pipelined tokens are discarded by the drain's identity check).
        Partial outputs stay on the request (status ``"cancelled"``).
        Returns True if the request was found live, False otherwise
        (already finished, shed, or unknown)."""
        for r in self._queue:
            if r.rid == rid:
                self._queue.remove(r)
                self._event("cancel", r)
                self._terminal_queued(r, "cancelled")
                if self._m is not None:
                    self._m.queue_depth.set(len(self._queue))
                return True
        for slot, r in enumerate(self._kv.reqs):
            if r is not None and r.rid == rid:
                self._event("cancel", r, slot=slot)
                self._retire(slot, "cancelled")
                return True
        return False

    def _expire_deadlines(self):
        """Retire every request whose ``deadline_ms`` has passed — queued
        requests never reach a slot; resident ones (mid-prefill or
        decoding) free their slot for re-admission this same step."""
        now = time.perf_counter()
        expired = [r for r in self._queue
                   if r._t_deadline is not None and now >= r._t_deadline]
        for r in expired:
            self._queue.remove(r)
            self._terminal_queued(r, "timed_out")
        if expired and self._m is not None:
            self._m.queue_depth.set(len(self._queue))
        for slot, r in enumerate(self._kv.reqs):
            if r is not None and r._t_deadline is not None \
                    and now >= r._t_deadline:
                self._retire(slot, "timed_out")

    # ------------------------------------------------- faults and retries
    def _inject_nan(self, slot):
        """Fault seam (FaultPlan poison): overwrite the slot's first
        cached key row (layer 0, position 0 — attended by every later
        query of the slot) with NaN, eagerly between compiled steps.
        Functional ``.at[].set`` touches only that row, so cohabiting
        slots' cache bytes are untouched — the quarantine's
        byte-identity guarantee rests on per-row attention isolation.
        Paged engines poison the slot's FIRST MAPPED BLOCK instead (the
        pool has no per-slot rows); the seam is test-only and the paged
        fault tests use distinct prompts, so the poisoned block is never
        a shared prefix block.

        int8 caches can't hold a NaN in the data leaf — the poison lands
        in the SCALE leaf instead (same row indices minus the trailing
        ``D`` axis): a NaN scale dequantizes the row to NaN, which
        reaches the logits exactly like a NaN float row."""
        if self._state_idx:
            # a model with recurrent state beside its K/V rows: poison the
            # slot's first state leaf instead — every later token of the
            # slot reads it, and the quarantine has to cover it too
            layer = list(self._kv.caches[0])
            i = self._state_idx[0]
            layer[i] = layer[i].at[(slot,) + (0,) * (layer[i].ndim - 1)] \
                .set(jnp.nan)
            self._kv.caches[0] = tuple(layer)
            return
        k, *rest = self._kv.caches[0]       # the first rows leaf

        def poison(leaf, *idx):
            if isinstance(leaf, tuple):
                return (leaf[0], leaf[1].at[idx].set(jnp.nan))
            return leaf.at[idx].set(jnp.nan)

        if self._paged:
            b = int(self._kv.block_tables[slot, 0])
            if b >= self._kv.num_blocks:
                return   # no rows mapped yet (unreachable: _apply_poison
                         # already defers slots with no chunk dispatched)
            self._kv.caches[0] = (poison(k, b, 0), *rest)
            return
        self._kv.caches[0] = (poison(k, slot, 0), *rest)

    def _apply_poison(self):
        """Inject every due NaN payload from the fault plan.  Injection
        waits until the slot has at least one cache row written (a
        mid-prefill slot at offset 0 would have its poison overwritten by
        its own first chunk)."""
        f = self._faults
        if f is None or not f.poison:
            return
        for slot, r in enumerate(self._kv.reqs):
            if r is None or not f.poison_due(r.rid, self._step_idx):
                continue
            st = self._pf.get(slot)
            if st is not None and st["off"] == 0:
                continue   # no rows written yet — defer to a later step
            self._inject_nan(slot)
            f.mark_poisoned(r.rid)
            self._event("poison", r, slot=slot)

    def _apply_host_corrupt(self):
        """Inject every due ``FaultPlan(host_tier_corrupt=...)`` payload:
        damage the host-tier entries along a token chain (or every entry)
        so the NEXT restore exercises the validation + suffix-prefill
        fallback path.  No-op without a host tier — the plan's damage
        lands on stored bytes only, never the device pool."""
        f = self._faults
        if (f is None or not f.host_tier_corrupt or not self._paged
                or self._kv.host_tier is None):
            return
        for tokens, mode in f.host_corrupts_due(self._step_idx):
            n = self._kv.corrupt_host(tokens, mode=mode)
            self._event("host_corrupt", mode=mode, entries=n)

    def _fault_point(self, kind, attempt):
        if self._faults is not None:
            self._faults.maybe_dispatch_error(kind, self._step_idx,
                                              attempt)

    def _retry(self, fn, what):
        """Bounded dispatch/drain retry: run ``fn(attempt)`` up to
        ``retry_attempts`` times against transient
        ``XlaRuntimeError``-class failures, backing off exponentially
        through the sanctioned ``_backoff_sleep``; exhaustion re-raises
        the last error.  ``fn`` must be side-effect-free until it
        returns (the engine's fault points raise BEFORE the real
        dispatch), so a retried attempt re-issues an identical program
        and the run's outputs stay byte-identical to an unfaulted one."""
        delay = self._retry_backoff
        for attempt in range(self._retry_attempts):
            try:
                return fn(attempt)
            except _RETRYABLE as e:
                if attempt + 1 >= self._retry_attempts:
                    # exhaustion: the engine is about to surface a device
                    # error to the caller — snapshot the path that led here
                    if self._fr is not None:
                        self._event("retry", what=what, attempt=attempt + 1,
                                    error=type(e).__name__, exhausted=True)
                        self._fr.auto_dump("retry_exhausted")
                    raise
                if self._m is not None:
                    self._m.dispatch_retries.inc()
                self._event("retry", what=what, attempt=attempt + 1,
                            error=type(e).__name__)
                _LOG.warning(
                    "serving %s failed (%s: %s) — retrying "
                    "(attempt %d/%d) after %.3fs backoff",
                    what, type(e).__name__, e, attempt + 1,
                    self._retry_attempts - 1, delay)
                _backoff_sleep(delay)
                delay *= 2

    def _fetch(self, kind, *arrays):
        """``_host_fetch`` behind the bounded retry + fault seam: the
        drain-side twin of the dispatch retry (re-fetching the same
        device futures is idempotent)."""
        def go(attempt):
            self._fault_point(kind, attempt)
            return _host_fetch(*arrays)
        return self._retry(go, kind)

    # ------------------------------------------------------ instrumentation
    # ONE call per boundary: the flight-recorder event, the request's
    # timeline mark and (for a phase) the span on the profiler's timeline
    # all come from the same call, so they cannot drift apart.
    def _event(self, kind, req=None, mark=None, **detail):
        """One lifecycle edge: the flight-recorder event ``kind`` at this
        step and, for one of ``req``'s own transitions, its timeline mark
        ``mark`` (carrying ``slot`` / ``chunk`` / ``final``).  Nothing with
        the recorder off.  Host bookkeeping only."""
        fr = self._fr
        if fr is None:
            return None
        if req is None:
            rid = detail.pop("rid", None)
        else:
            rid = req.rid
            if mark is not None and req._trace is not None:
                req._trace.mark(mark, **{
                    k: detail[k] for k in _MARK_KEYS
                    if detail.get(k) is not None})
        return fr.record(kind, step=self._step_idx, rid=rid,
                         slot=detail.pop("slot", None), **detail)

    def _phase(self, name, req=None, mark=None, observe=None, **detail):
        """Context manager around one boundary of the step loop: the span
        ``serving.<name>`` (an event of the profiler's timeline when a
        session is on, a flag test when none is), the flight-recorder
        event ``name`` with the same details — stamped with the phase's
        ``seconds`` when it ends — and ``req``'s timeline mark.
        ``observe`` (a histogram child) is fed the seconds too."""
        ev = self._event(name, req, mark, **detail)
        if req is not None:
            detail["rid"] = req.rid
        return _Phase(span("serving." + name, step=self._step_idx, **detail),
                      ev, observe)

    def _init_phase(self, name):
        """``_phase`` for a part of construction: its span is the start-up
        record's ``phase`` (a span AND an entry of the in-memory log,
        observability/compilecache.py), so what construction cost is read
        from the program's own record after the fact."""
        return _Phase(startup_phase("serving." + name, step=self._step_idx),
                      self._event(name), None)

    # --------------------------------------------------- program dispatch
    # the four compiled entry points behind ONE seam: mesh=None dispatches
    # the module-level single-device jits (bitwise the pre-mesh engine);
    # a mesh dispatches the cached TP programs (serving/sharding.py —
    # statics baked in at construction).  Both take and return replicated
    # host-facing operands, so every caller is placement-oblivious.
    def _kv_event(self, kind, **info):
        """PagedKVCacheManager event hook: mirror allocator + host-tier
        activity (``block_alloc`` / ``block_free`` / ``demote`` /
        ``restore`` / ``host_evict`` / ``host_error``) into the flight
        recorder and keep the block-pool and host-tier gauges current.
        Host bookkeeping only — the hook never touches a device value."""
        self._event(kind, **info)
        if self._m is not None:
            draft_used = self._kv.draft_blocks_used()
            self._m.set_kv_blocks(
                self._kv.blocks_used() - draft_used, draft_used,
                self._kv.free_count())
            host = getattr(self._kv, "host_tier", None)
            if host is not None:
                self._m.kv_host_blocks.set(host.n_blocks)
                self._m.kv_host_bytes.set(host.total_bytes)
                if kind == "demote":
                    self._m.tier_demotions.inc(info.get("n_blocks", 1))
                elif kind == "restore":
                    self._m.tier_restores.inc(info.get("n_blocks", 1))
                elif kind == "host_error":
                    self._m.host_tier_errors.inc()

    def _tables(self):
        """The block-table operand for one dispatch: the host mirror
        shipped as a fixed-shape ``[B, W]`` traced array (never a Python
        list — tpu-lint PTL010 polices the difference)."""
        return self._kv.device_tables()

    def _decode_lengths(self, active):
        """The lengths operand of one decode dispatch (every slot that is
        not ``active`` parked), counted on the way: the rows one layer's
        cache read touches at these lengths against the rows its live
        slots attend to (``serving_kv_rows_*_total``) and, for a family
        with recurrent state, the slots whose state one layer's update
        reads against those it skips (``serving_state_slots_*_total``) —
        a few integer operations on the host's mirror, nothing on the
        device.  Spec rounds count their ``k + 1`` verify tokens at the
        mirror's lengths, which trail the device's by the round in
        flight."""
        m = self._m
        if m is not None:
            spec = self._mode == "spec"
            # the tree verify of a draft model attends under a bias
            plain = not (self._paged or self._q8 or (spec and self._dspec))
            for step in range(1 if spec else self._sync):
                lengths = np.where(active, self._kv.lengths + step,
                                   self._kv.max_len)
                read, live = kv_rows_read(
                    lengths, self._spec_k + 1 if spec else 1, self._chunk,
                    self._kv.max_len, plain)
                m.kv_rows_read.inc(read)
                m.kv_rows_live.inc(live)
                if self._state_idx:
                    # the update's own rule: a slot is live below Lmax
                    slots = int(np.sum(lengths < self._kv.max_len))
                    m.state_slots_read.inc(slots)
                    m.state_slots_skipped.inc(len(lengths) - slots)
        return self._kv.device_lengths(active)

    def _call_decode(self, cur, dev_len):
        if self._tp is not None:
            if self._paged:
                return self._tp.decode_steps(self._params, cur,
                                             self._kv.caches, dev_len,
                                             self._tables())
            return self._tp.decode_steps(self._params, cur,
                                         self._kv.caches, dev_len)
        return self._fam.decode_steps(
            self._params, self._cfg, cur, self._kv.caches, dev_len,
            n_steps=self._sync, chunk_size=self._chunk,
            block_tables=self._tables() if self._paged else None,
            program_key=self._pk)

    def _call_spec(self, cur, dev_len, active, k=None):
        """One speculative round at draft depth ``k`` (``None`` = the
        configured ceiling).  Returns the SAME 8-tuple for both draft
        sources — (emitted, j, cur', new_len, ok, caches, hist,
        hist_len) — so the two call sites stay source-oblivious: the
        draft-model path stashes its dense draft caches as engine state
        and passes the (unused) history straight through."""
        k = self._spec_k if k is None else k
        pk = (self._pk if k == self._spec_k
              else self._pk.replace(spec_depth=k))
        if self._dspec:
            if self._tp is not None:
                tp = self._tp_spec[k]
                if self._paged:
                    out = tp.spec_draft_step(
                        self._params, self._dparams, cur, self._kv.caches,
                        dev_len, active, self._tables(),
                        self._kv.device_draft_tables())
                else:
                    out = tp.spec_draft_step(
                        self._params, self._dparams, cur, self._kv.caches,
                        self._dcaches, dev_len, active)
            else:
                out = self._fam.spec_draft_step(
                    self._params, self._dparams, self._cfg, self._dcfg,
                    cur, self._kv.caches,
                    None if self._paged else self._dcaches, dev_len,
                    active, spec_k=k, chunk_size=self._chunk,
                    block_tables=self._tables() if self._paged else None,
                    draft_tables=(self._kv.device_draft_tables()
                                  if self._paged else None),
                    program_key=pk)
            emitted, j, cur2, new_len, ok, caches, dc = out
            if not self._paged:
                self._dcaches = list(dc)
            return (emitted, j, cur2, new_len, ok, caches, self._hist,
                    self._hist_len)
        if self._tp is not None:
            tp = self._tp_spec[k]
            if self._paged:
                return tp.spec_step(self._params, cur,
                                    self._kv.caches, dev_len,
                                    self._hist, self._hist_len,
                                    active, self._tables())
            return tp.spec_step(self._params, cur, self._kv.caches,
                                dev_len, self._hist, self._hist_len,
                                active)
        return self._fam.spec_step(
            self._params, self._cfg, cur, self._kv.caches, dev_len,
            self._hist, self._hist_len, active, spec_k=k,
            chunk_size=self._chunk,
            block_tables=self._tables() if self._paged else None,
            program_key=pk)

    def _call_prefill_chunk(self, tokens, offset, prompt_len, slot):
        if self._tp is not None:
            if self._paged:
                return self._tp.prefill_chunk(self._params, tokens, offset,
                                              prompt_len, self._kv.caches,
                                              slot, self._hist,
                                              self._hist_len,
                                              self._tables())
            return self._tp.prefill_chunk(self._params, tokens, offset,
                                          prompt_len, self._kv.caches,
                                          slot, self._hist, self._hist_len)
        return self._fam.prefill_chunk(
            self._params, self._cfg, tokens, offset, prompt_len,
            self._kv.caches, slot, hist=self._hist,
            hist_len=self._hist_len, with_hist=self._mode == "spec",
            chunk_size=self._chunk,
            block_tables=self._tables() if self._paged else None,
            program_key=self._pk)

    def _call_draft_prefill_chunk(self, chunk, off, plen, slot):
        """One DRAFT-model prefill chunk: fills the draft tenant's KV for
        the prompt rows the draft decode scan will attend.  Paged engines
        run it over the shared pool's first ``d`` layer arrays through
        the draft block tables (the target's pool list is re-assembled
        around the returned layers — serving_prefill_chunk donates its
        cache operand); dense engines write the separate ``_dcaches``.
        The chunk's first-token/finite outputs are dropped: draft KV is
        advisory (a bad draft row costs accept rate, never output
        bytes)."""
        d = len(self._dparams["layers"])
        if self._tp is not None:
            if self._paged:
                _, _, new_dc, _, _ = self._tp.draft_prefill_chunk(
                    self._dparams, jnp.asarray(chunk),
                    jnp.asarray(off, jnp.int32), plen,
                    self._kv.caches[:d], jnp.asarray(slot, jnp.int32),
                    self._kv.device_draft_tables())
            else:
                _, _, new_dc, _, _ = self._tp.draft_prefill_chunk(
                    self._dparams, jnp.asarray(chunk),
                    jnp.asarray(off, jnp.int32), plen,
                    self._dcaches, jnp.asarray(slot, jnp.int32))
        else:
            _, _, new_dc, _, _ = self._fam.prefill_chunk(
                self._dparams, self._dcfg, jnp.asarray(chunk),
                jnp.asarray(off, jnp.int32), plen,
                self._kv.caches[:d] if self._paged else self._dcaches,
                jnp.asarray(slot, jnp.int32), with_hist=False,
                chunk_size=self._chunk,
                block_tables=(self._kv.device_draft_tables()
                              if self._paged else None),
                program_key=self._pk)
        if self._paged:
            self._kv.caches = list(new_dc) + self._kv.caches[d:]
        else:
            self._dcaches = list(new_dc)

    # ------------------------------------------------ adaptive draft depth
    def _reset_spec_slot(self, slot):
        """Fresh request in ``slot``: restart its accept-rate window and
        return its desired rung to the ceiling (a new prompt's
        draftability is unknown — start at full depth, degrade on
        evidence)."""
        if self._awin is not None:
            self._awin[slot].reset()
            self._k_want[slot] = len(self._k_rungs) - 1

    def _adapt_k(self, rounds, k):
        """Feed one drained verify round into the adaptive-k policy:
        per-slot windows absorb (k drafted, j accepted), hysteresis
        moves each slot's desired rung (>= 80% of the window accepted:
        one rung deeper; <= 40%: one rung shallower).  Host arithmetic
        only — the chosen batch depth is read at the NEXT dispatch."""
        if self._awin is None:
            return
        for slot, j in rounds:
            w = self._awin[slot]
            w.push(k, j)
            r = w.rate()
            if r is None or len(w) < w.window:
                continue
            if r >= 0.8 and self._k_want[slot] < len(self._k_rungs) - 1:
                self._k_want[slot] += 1
            elif r <= 0.4 and self._k_want[slot] > 0:
                self._k_want[slot] -= 1

    def _next_k(self, live):
        """The batch depth for the NEXT spec dispatch: the most
        conservative live slot's desired rung (one program serves the
        whole batch — a deep k wastes dead verify lanes on every hard
        slot), approached ONE rung per round so a retiring pessimist
        never yanks the batch straight to the ceiling."""
        if self._awin is None or not live:
            return self._k_cur
        want = min(self._k_want[i] for i in live)
        cur = self._k_rungs.index(self._k_cur)
        nxt = cur + (1 if want > cur else -1 if want < cur else 0)
        self._k_cur = self._k_rungs[nxt]
        if self._m is not None:
            self._m.spec_draft_k.set(self._k_cur)
        return self._k_cur

    def _admit(self):
        """Chunked admission: assign freed slots and queue each prompt for
        incremental chunk dispatch (``_spend_prefill``).  Nothing here
        touches the device, so admission itself never stalls the loop —
        the prompt work is spread over the following scheduler steps under
        ``prefill_budget``.

        Paged engines budget TOKENS, not slots: admission reserves the
        request's worst-case block count (prompt + max_new + headroom,
        clamped to max_len, minus any radix-matched prefix) and DEFERS the
        queue head when the pool can't cover it — FIFO, so a smaller later
        request never starves the head.  A prefix hit adopts the matched
        blocks and starts prefill at the suffix offset; when the prefill
        chunk is wider than the kv block the match is aligned DOWN to a
        chunk boundary so the suffix decomposes into the exact same
        compiled chunks a miss would run (byte-identity across hit/miss)."""
        free = self._kv.free_slots()
        if not free or not self._queue:
            return
        m = self._m
        P = self._pchunk
        while free and self._queue:
            # priority-aware head: the highest-priority waiter admits
            # first.  max() is stable, so all-default traffic keeps the
            # exact FIFO order (and bytes) of the pre-priority engine;
            # the paged defer below still BREAKS, so held-back capacity
            # protects the head's class instead of leaking to smaller
            # later requests.  ``tok`` is the (re-)admission sequence —
            # for a preemption resume it includes every emitted token,
            # so the radix match re-adopts the parked chain and prefill
            # runs only the suffix.
            r = max(self._queue, key=lambda q: q.priority)
            tok = self._admission_ids(r)
            off0, shared, budget, need, host_tok = 0, [], 0, 0, 0
            doff0, dshared, dbudget = 0, [], 0
            if self._paged:
                C = self._kv.block
                p = int(tok.size)
                rem = max(1, r.max_new_tokens - len(r.output_ids))
                need = min(self._lmax, p + rem + self._headroom())
                if self._prefill_only:
                    # no decode ever writes past the prompt here: the
                    # chain budget is exactly the prompt's own blocks,
                    # which is the capacity win admission throughput
                    # rides on a dedicated prefill worker
                    need = p
                off0, shared = self._kv.match_prefix(tok)
                # restore-on-adopt: when the device radix breaks before
                # the match cap, rehydrate the host tier's continuation
                # (a device_put of stored rows, cheaper than suffix
                # prefill past ~1 block) and re-run the ordinary radix
                # match — restored blocks park exactly like a released
                # chain, so admission below is tier-oblivious
                host = self._kv.host_tier
                off_dev = off0
                if (host is not None and host.n_blocks
                        and len(shared) < (p - 1) // C):
                    t0 = time.perf_counter()
                    got = self._kv.restore_from_host(
                        tok, rid=r.rid, min_blocks=self._host_min_blocks)
                    if got:
                        if m is not None:
                            m.tier_restore_seconds.observe(
                                time.perf_counter() - t0)
                        off0, shared = self._kv.match_prefix(tok)
                if P > C:
                    off0 = (off0 // P) * P
                    shared = shared[:off0 // C]
                host_tok = max(0, off0 - min(off_dev, off0))
                budget = -(-need // C) - len(shared)
                if self._dspec:
                    # draft tenancy: the draft chain needs the same block
                    # count (shared pool, own tables/namespace), reserved
                    # up front so a mid-stream OOM can't strand a slot
                    # with target KV but no draft KV
                    doff0, dshared = self._kv.match_draft_prefix(tok)
                    if P > C:
                        doff0 = (doff0 // P) * P
                        dshared = dshared[:doff0 // C]
                    dbudget = -(-need // C) - len(dshared)
                if not self._kv.can_reserve(budget + dbudget):
                    self._event("admit_defer", r,
                                need_blocks=budget + dbudget)
                    break
            self._queue.remove(r)
            slot = free.pop(0)
            # the prompt's power-of-two class: the label of the per-bucket
            # prefill counter and the admit event
            bucket = min(self._lmax, max(
                16, 1 << (int(r.prompt_ids.size) - 1).bit_length()))
            with self._phase("admit", r, mark="prefilling", slot=slot,
                             bucket=bucket):
                self._kv.assign(slot, r)
                self._reset_spec_slot(slot)
                p = int(tok.size)
                if self._paged:
                    self._kv.adopt_prefix(slot, shared)
                    if self._dspec:
                        self._kv.adopt_draft_prefix(slot, dshared)
                    self._kv.reserve(slot, budget + dbudget)
                    self._need_rows[slot] = need
                    r._adm_ids = tok
                    self._n_prompt_tokens += p
                    self._n_reuse_tokens += off0
                    self._n_host_reuse_tokens += host_tok
                if r.preempts:
                    # preemption resume: the adopted chain covers [0, off0) —
                    # the suffix is the whole recompute cost
                    self._n_resume_suffix += p - off0
                    self._n_resume_total += p
                    self._event("resume", r, slot=slot,
                                suffix_tokens=p - off0, total_tokens=p)
                    if m is not None:
                        m.preempt_resume_tokens.inc(p - off0)
                padded = np.zeros((-(-p // P) * P,), np.int32)
                padded[:p] = tok
                if off0:
                    # prefix hit: the adopted blocks already hold rows
                    # [0, off0) — prefill starts at the suffix offset
                    self._event("prefix_hit", r, slot=slot, tokens=off0,
                                host_tokens=host_tok)
                    if m is not None:
                        m.prefix_reuse_tokens.inc(off0)
                        if off0 > host_tok:
                            m.prefix_hit("device")
                        if host_tok:
                            m.prefix_hit("host")
                    if self._mode == "spec":
                        # the skipped chunks would have written hist rows
                        # [0, off0); rebuild the slot's whole prompt row
                        # eagerly.  Draft quality only — emission is always
                        # the verify forward's own greedy picks (lossless),
                        # so output bytes never depend on hist contents
                        row = np.zeros((self._lmax,), np.int32)
                        w = min(padded.size, self._lmax)
                        row[:w] = padded[:w]
                        self._hist = self._hist.at[slot].set(jnp.asarray(row))
                if self._n_experts:
                    # one row a position: an admission prefills (and
                    # records) everything from row off0 again
                    r.routes = _head_rows(r.routes, off0)
                # device-ready prompt length, built here (outside the chunk
                # dispatch loop) so _spend_prefill stays sync-free
                self._pf[slot] = {"req": r, "tok": padded, "p": p, "off": off0,
                                  "doff": doff0, "first": None, "okf": None,
                                  "plen": jnp.asarray(np.array([p], np.int32))}
                if m is not None:
                    m.admitted.inc()
                    m.prefill(bucket)
                    if self._paged:
                        m.prompt_tokens.inc(p)
                    m.queue_wait.observe(time.perf_counter() - r.t_submit)
        if m is not None:
            m.queue_depth.set(len(self._queue))
            m.slots_occupied.set(self._kv.occupied())
            m.live_tokens.set(self._kv.live_tokens())

    # ---------------------------------------------- disaggregated adoption
    # the decode-worker half of a prefill/decode split (serving/disagg.py):
    # a request whose prefill ran on ANOTHER engine enters here with its
    # first token and its exported block chain, bypassing _admit/_pf
    # entirely.  From the next decode dispatch on, the slot is
    # indistinguishable from a locally prefilled one — same cur / length /
    # block-table VALUES, no new shapes — which is both the byte-identity
    # and the zero-retrace argument for migration.

    def can_adopt(self, request):
        """Whether ``adopt_prefilled`` would succeed right now: a free
        slot plus pool capacity for the imported chain AND the decode
        growth budget.  The coordinator gates on this BEFORE paying for
        a transfer — a deferred migration costs nothing."""
        if not self._paged or self._prefill_only:
            return False
        if not self._kv.free_slots():
            return False
        p = int(request.prompt_ids.size)
        rem = max(1, request.max_new_tokens - len(request.output_ids))
        need = min(self._lmax, p + rem + self._headroom())
        return self._kv.can_reserve(
            -(-need // self._kv.block) * (2 if self._dspec else 1))

    def adoption_viable(self, request):
        """The static half of ``can_adopt``: could this request EVER fit
        this engine (worst-case rows within ``max_len``)?  The coordinator
        sheds statically-impossible requests at submit time — a
        ``can_adopt`` False only ever means *defer and retry*, never
        *abort*."""
        return (int(request.prompt_ids.size) + request.max_new_tokens
                + self._headroom() <= self._lmax)

    def adopt_prefilled(self, request, first, leaves):
        """Admit ``request`` with its prefill already done elsewhere:
        import the transfer ``leaves`` into fresh pool blocks, splice
        them under a free slot's table row, and seed the decode carry
        (cur = ``first``, length = prompt) exactly where a local prefill
        would have left it.  The request must already hold its first
        token — the coordinator emits it at migration start, so TTFT
        rides the handoff, never the adoption.  Raises on capacity
        (callers gate on ``can_adopt``); a failed import rolls its
        blocks back (kv_cache.import_chain).  Returns the slot."""
        if not self._paged:
            raise ValueError(
                "adopt_prefilled requires a paged engine "
                "(the block pool IS the migration transfer unit)")
        if self._prefill_only:
            raise ValueError("a prefill-only engine cannot adopt decode "
                             "work")
        if not request.output_ids:
            raise ValueError("adopt_prefilled: the request must already "
                             "hold its migrated first token")
        free = self._kv.free_slots()
        if not free:
            raise EngineOverloaded("no free slot to adopt into")
        tok = request.prompt_ids
        p = int(tok.size)
        rem = max(1, request.max_new_tokens - len(request.output_ids))
        need = min(self._lmax, p + rem + self._headroom())
        # rid bookkeeping mirrors submit(): the coordinator's rid is
        # kept, so flight-recorder events correlate across both workers
        if request.rid is None:
            request.rid = self._next_rid
            self._next_rid += 1
        else:
            if request.rid in self._rids:
                raise ValueError(
                    f"rid {request.rid!r} is already in use by another "
                    "request on this engine")
            if isinstance(request.rid, int):
                self._next_rid = max(self._next_rid, request.rid + 1)
        self._rids.add(request.rid)
        if request.t_submit is None:
            request.t_submit = time.perf_counter()
        if request.deadline_ms is not None \
                and request._t_deadline is None:
            request._t_deadline = request.t_submit \
                + request.deadline_ms / 1e3
        slot = free[0]
        blocks = self._kv.import_chain(leaves)  # all-or-nothing
        self._kv.assign(slot, request)
        self._reset_spec_slot(slot)
        self._kv.splice_chain(slot, blocks)
        resv = -(-need // self._kv.block) - len(blocks)
        doff0, dshared = 0, []
        if self._dspec:
            # the transfer carries only TARGET KV (the draft's is cheap
            # to rebuild and model-specific); the draft chain starts from
            # whatever its own radix namespace already holds
            C = self._kv.block
            doff0, dshared = self._kv.match_draft_prefix(tok)
            P = self._pchunk
            if P > C:
                doff0 = (doff0 // P) * P
                dshared = dshared[:doff0 // C]
            resv += -(-need // C) - len(dshared)
            self._kv.adopt_draft_prefix(slot, dshared)
        self._kv.reserve(slot, resv)
        self._need_rows[slot] = need
        self._kv.lengths[slot] = p
        request._adm_ids = tok
        self._n_prompt_tokens += p
        self._cur[slot] = int(first)
        self._adm_pending.add(slot)
        if self._mode == "spec":
            # rebuild the draft-history row the final prefill chunk
            # would have written: prompt at [0, p), first at p, frontier
            # p + 1.  Draft quality only — emission is always the verify
            # forward's own picks, so output bytes never depend on it
            row = np.zeros((self._lmax,), np.int32)
            w = min(p, self._lmax)
            row[:w] = tok[:w]
            if p < self._lmax:
                row[p] = int(first)
            self._hist = self._hist.at[slot].set(jnp.asarray(row))
            self._hist_len = self._hist_len.at[slot].set(p + 1)
        if self._dspec:
            # rebuild the draft model's prompt KV locally, off the step
            # path (adoption is already a slow-path handoff): chunked
            # draft prefill over the suffix the draft radix didn't cover
            P = self._pchunk
            padded = np.zeros((-(-p // P) * P,), np.int32)
            padded[:p] = tok
            plen = jnp.asarray(np.array([p], np.int32))
            off = doff0
            while off < p:
                self._kv.ensure_draft_rows(slot, min(off + P, p))
                self._call_draft_prefill_chunk(
                    padded[off:off + P][None, :], off, plen, slot)
                off += P
            self._kv.register_draft_prefix(slot, tok)
        # the imported chain is as good as a local prefill's (its finite
        # check passed before export): publish it so later identical
        # prompts on THIS worker reuse it — prefix reuse survives
        # migration
        self._kv.register_prefix(slot, tok)
        self._attach_trace(request)
        if request._trace is not None:
            request._trace.mark("decoding", slot=slot)
        if self._m is not None:
            self._m.admitted.inc()
            self._m.prompt_tokens.inc(p)
            self._m.prefix_hit("fleet")
            self._m.slots_occupied.set(self._kv.occupied())
            self._m.live_tokens.set(self._kv.live_tokens())
        return slot

    def _spend_prefill(self):
        """Spend up to ``prefill_budget`` chunks of prompt across the
        slots mid-prefill, admission order first (the earliest admission
        reaches its first token soonest).  Every run dispatch is async
        and feeds off device-resident state (the carried caches / hist /
        write offset) — the loop never syncs, the tpu-lint PTL004 rule
        polices that.  A slot whose FINAL chunk went out leaves the
        prefilling state: it joins the very next decode dispatch with its
        device-resident first token, and the host copy is emitted at the
        next drain.  Returns the number of chunks spent."""
        if not self._pf:
            return 0
        with self._phase("spend_prefill", prefilling=len(self._pf)):
            return self._spend_chunks()

    def _run_widths(self):
        """The widths, in chunks, ONE prefill run may take: 1 and every
        further power of two up to what a step may spend
        (``prefill_budget``) and the longest prompt holds — the ladder
        bounds the compiled prefill programs at ``log2(budget) + 1``, as
        ``_k_rungs`` bounds the draft depths.  The one place that says
        where the geometry keeps a run at one chunk: a run is whole chunks
        from a chunk boundary, so what the constructor demands of
        ``prefill_chunk`` as a multiple (whole SSD chunks, whole kv
        blocks) holds for every width, and the reference append scatters
        row by row at any offset; but a run starts where the last one
        ended, NOT on a boundary of its own rows, and the fused prefill
        kernel's appends (``prefill_impl="pallas"``) are DMA windows that
        rely on ``offset % rows == 0`` — there a run stays one chunk."""
        top = min(self._pbudget, -(-self._lmax // self._pchunk))
        if self._prefill_impl == "pallas":
            top = 1
        return [1 << i for i in range(top.bit_length())]

    def _run_tokens(self, st, off, k):
        """The ``[1, k * P]`` prompt rows of a run from ``off`` (zeros
        past the padded prompt: a rehearsal wider than what is left)."""
        n = k * self._pchunk
        tok = st["tok"][off:off + n]
        if tok.size < n:
            tok = np.pad(tok, (0, n - tok.size))
        return tok[None, :]

    def _prefill_run(self, slot, st, k):
        """Dispatch ONE run of the prefill program over the slot's next
        ``k`` chunks, carrying the donated caches (and the draft history
        in spec mode).  Returns (first, ok, [routes])."""
        first, okf, self._kv.caches, hist, hist_len, *routes = \
            self._call_prefill_chunk(
                jnp.asarray(self._run_tokens(st, st["off"], k)),
                jnp.asarray(st["off"], jnp.int32), st["plen"],
                jnp.asarray(slot, jnp.int32))
        if self._mode == "spec":
            self._hist, self._hist_len = hist, hist_len
        return first, okf, routes

    def _draft_prefill_run(self, slot, st, k):
        """The same for the resident draft model's own cursor."""
        self._call_draft_prefill_chunk(
            self._run_tokens(st, st["doff"], k), st["doff"], st["plen"],
            slot)

    def _rehearse_widths(self, slot, st, k):
        """The engine's first prefill run: run every OTHER width of the
        ladder at the same ``(slot, offset)`` just before it, so that by
        the end of this scheduler step every prefill program a later step
        can dispatch (target and draft) is compiled and in the jit's
        cache under the very operands later runs bring — the window of a
        service never compiles for a prompt length its warm-up did not
        happen to draw.  A run is idempotent on what it writes: the real
        runs rewrite the prompt's rows, rows past the prompt are invisible
        (and drop past ``max_len``; on unmapped blocks when paged), a
        family's recurrent state is reset inside the run at offset 0 —
        where an engine's first run always is for a family that has one —
        and the draft history's frontier is set again by the final run.
        Nothing of a rehearsal is counted, marked or recorded."""
        for w in self._widths:
            if w == k:
                continue
            if st["off"] < st["p"]:
                self._prefill_run(slot, st, w)
            if self._dspec and st["doff"] < st["p"]:
                self._draft_prefill_run(slot, st, w)
        self._widths_warm = True

    def _spend_chunks(self):
        m = self._m
        P = self._pchunk
        budget = self._pbudget
        spent = 0
        for slot in list(self._pf):
            if not budget:
                break
            st = self._pf[slot]
            req, p = st["req"], st["p"]
            while budget:
                # ONE run takes as many whole chunks as the budget and
                # the prompt have left (the widest rung of the ladder
                # that fits): the weights are read once for all of them.
                # The draft model's cursor is independent — a
                # target-side radix hit skips chunks the draft may still
                # need — and rides the same budget unit: k target chunks
                # + k draft chunks per spend of k
                cursors = (st["off"], st["doff"]) if self._dspec \
                    else (st["off"],)
                left = [-(-(p - off) // P) for off in cursors if off < p]
                k = max(w for w in self._widths if w <= min([budget] + left))
                if not self._widths_warm:
                    self._rehearse_widths(slot, st, k)
                if st["off"] < p:
                    c0, last = st["off"] // P, (p - 1) // P
                    if self._fr is not None and req._trace is not None:
                        # one mark a CHUNK whatever the run's width, all
                        # at the run's dispatch: a request's marks say
                        # how much of its prompt was spent when
                        for c in range(c0, c0 + k):
                            req._trace.mark("prefilling", slot=slot,
                                            chunk=c, final=c == last)
                    with self._phase(
                            "prefill_chunk", req, slot=slot, chunk=c0,
                            chunks=k, final=c0 + k > last):
                        if self._paged:
                            # map the run's REAL rows before its writes
                            # dispatch (pad columns past the prompt drop
                            # on the sentinel); draws down the
                            # reservation made at admission
                            self._kv.ensure_rows(
                                slot, min(st["off"] + k * P, p))
                        if (m is not None and self._state_idx
                                and st["off"] == 0):
                            # the family's program resets the slot's
                            # recurrent state inside this run
                            m.state_resets.inc()
                        first, okf, routes = self._prefill_run(slot, st, k)
                    if routes:
                        # one record a RUN, with its real rows;
                        # .preempts: the admission these rows belong to
                        self._chunk_routes.append(
                            (req, min(k * P, p - st["off"]), routes[0],
                             req.preempts))
                    st["off"] += k * P
                    if m is not None:
                        m.prefill_chunks.inc(k)
                        m.prefill_runs.inc()
                    if st["off"] >= p:
                        # only the FINAL chunk's finite flag is meaningful
                        # (its query attends the whole prefix) — it rides
                        # with the first token and is checked at emission
                        st["first"], st["okf"] = first, okf
                if self._dspec and st["doff"] < p:
                    if self._paged:
                        self._kv.ensure_draft_rows(
                            slot, min(st["doff"] + k * P, p))
                    self._draft_prefill_run(slot, st, k)
                    st["doff"] += k * P
                budget -= k
                spent += k
                if st["off"] >= p and (
                        not self._dspec or st["doff"] >= p):
                    del self._pf[slot]
                    self._kv.lengths[slot] = p
                    self._dev_first[slot] = st["first"]
                    self._pending_firsts.append(
                        (slot, req, st["first"], st["okf"]))
                    break
        if m is not None:
            m.prefill_backlog.set(sum(
                -(-max(0, st["p"] - st["off"]) // P)
                for st in self._pf.values()))
        return spent

    def _flush_firsts(self):
        """A prefill-only engine's drain: block ONCE on the wave of
        pending final chunks and emit (an engine that decodes instead
        rides them on its next inflight record, fetched with its
        tokens)."""
        if not self._pending_firsts:
            return 0
        pend, self._pending_firsts = self._pending_firsts, []
        with self._phase("drain.wait", firsts=len(pend)):
            vals = self._fetch(
                "drain", *(x for _, _, f, o in pend for x in (f, o)))
        emitted = 0
        for n, (slot, r, _, _) in enumerate(pend):
            fv, ov = vals[2 * n], vals[2 * n + 1]
            self._cur[slot] = int(fv[0])
            self._dev_first.pop(slot, None)
            if self._kv.reqs[slot] is not r:
                continue
            if not bool(ov[0]):
                self._retire(slot, "poisoned")
                continue
            if self._paged:
                # publish the prefix only now that the finite check passed
                # (registering at dispatch could publish poisoned blocks a
                # later radix hit would silently adopt); before _emit,
                # which may release the slot.  The ADMISSION ids, not the
                # prompt — a preemption resume's chain also covers the
                # tokens it re-prefilled
                self._kv.register_prefix(slot, r._adm_ids)
                if self._dspec:
                    self._kv.register_draft_prefix(slot, r._adm_ids)
            if self._on_prefilled is not None:
                # disagg handoff: the chain is registered and still
                # mapped — the coordinator exports it here; _emit
                # (max_new=1) then retires the slot on the normal path
                self._on_prefilled(r, slot, int(fv[0]))
            emitted += self._emit(slot, [int(fv[0])])
        return emitted

    def _emit(self, slot, toks):
        """Append emitted tokens to the slot's request, truncating at EOS /
        max_new_tokens; retires the slot when the request completes.
        Returns the number of tokens actually consumed."""
        r = self._kv.reqs[slot]
        m = self._m
        took = 0
        for t in toks:
            if r.done:
                break
            r.output_ids.append(int(t))
            took += 1
            if r.t_first is None:
                r.t_first = time.perf_counter()
                if m is not None:
                    m.ttft.observe(r.t_first - r.t_submit)
                self._event("first_token", r, mark="decoding", slot=slot)
            if len(r.output_ids) >= r.max_new_tokens or (
                    r.eos_token_id is not None
                    and int(t) == int(r.eos_token_id)):
                r.done = True
        if took:
            if m is not None:
                m.emitted.inc(took)
            if self._detok is not None:
                r.text = self._detok(list(r.output_ids))
            if r.stream_cb is not None:
                try:
                    if self._faults is not None:
                        self._faults.maybe_crash_stream_cb(self._step_idx)
                    r.stream_cb(r, r.output_ids[-took:])
                except Exception as e:
                    # a crashing user callback must not kill the scheduler
                    # loop mid-batch (every other live slot would lose its
                    # in-flight block): count the drop by exception type,
                    # log once per request, and keep decoding
                    if m is not None:
                        m.stream_cb_error(type(e).__name__)
                    if not r._cb_err_logged:
                        r._cb_err_logged = True
                        _LOG.warning(
                            "stream_cb for request %r raised %s: %s — "
                            "further errors from this request are "
                            "counted but not logged", r.rid,
                            type(e).__name__, e)
        if r.done:
            r.status = "done"
            r.t_done = time.perf_counter()
            self._kv.release(slot)
            self._finished.append(r)
            if m is not None:
                m.retired.inc()
                m.e2e.observe(r.t_done - r.t_submit)
                m.tpot.observe(r.tpot)
                m.slots_occupied.set(self._kv.occupied())
            self._on_terminal(r, "done", slot=slot)
        return took

    # ------------------------------------------------------------ step / run
    def step(self):
        """One scheduler iteration: retire/admit, then one compiled decode
        dispatch over every live slot.  Returns tokens emitted."""
        self._last_step_unix = time.time()
        m = self._m
        if m is not None:
            m.steps.inc()
            m.last_step_time.set(self._last_step_unix)
        self._step_idx += 1
        with self._phase("step"):
            return self._step_impl()

    def _step_impl(self):
        if self._faults is not None:
            stalled = self._faults.maybe_slow_step(self._step_idx)
            if stalled:
                self._event("stall", seconds=stalled, injected=True)
        self._expire_deadlines()
        self._apply_poison()
        self._apply_host_corrupt()
        self._maybe_preempt()
        self._admit()
        spent = self._spend_prefill()
        if self._prefill_only:
            out = self._flush_firsts()
            if any(self._decodable(i) for i in range(self._B)):
                raise RuntimeError(
                    "prefill-only engine reached a decode dispatch — a "
                    "resident request survived its first-token flush")
        else:
            # decode-interference flag for this iteration: chunks were
            # spent, or a prefill is still in progress
            adm_active = spent > 0 or bool(self._pf)
            # the double buffer: stash the record of the PREVIOUS
            # iteration's dispatch, issue the next dispatch, and only then
            # drain the stash — step N+1 is outstanding on the device while
            # step N's tokens are synced and its emit/retire bookkeeping
            # runs.  When _dispatch has nothing to issue (e.g. every slot
            # retired at the last drain) the stashed record is still
            # drained, so run() terminates.
            prev, self._inflight = self._inflight, None
            self._dispatch(adm_active)
            out = self._drain(prev)
        if self._paged:
            # materialize staged demotions BETWEEN steps: the eviction-time
            # gathers have long since finished behind the drained dispatch,
            # so this copies host<-device buffers without stalling the loop
            self._kv.pump_host_tier()
        return out

    def _observe_interference(self, adm_active, per_slot_tokens):
        """Feed ``serving_tpot_during_admission_seconds``: the per-token
        interval between this decode drain and the previous one, observed
        only while admission work (a chunked-prefill backlog) was in
        flight."""
        now = time.perf_counter()
        if self._m is not None:
            self._m.live_tokens.set(self._kv.live_tokens())
            if adm_active and self._t_lastdrain is not None:
                self._m.tpot_admission.observe(
                    (now - self._t_lastdrain) / max(1.0, per_slot_tokens))
        self._t_lastdrain = now

    def _ensure_decode_rows(self, live):
        """Paged: grow every live slot's block chain to cover the rows
        this decode dispatch may write — the host length mirror plus
        headroom (the mirror lags the device by at most one inflight
        dispatch, which headroom doubles to cover), capped by the token
        budget reserved at admission.  Must run BEFORE the dispatch reads
        the table operand; a no-op once the chain reaches the cap."""
        if not self._paged:
            return
        for i in live:
            upto = min(int(self._need_rows[i]),
                       int(self._kv.lengths[i]) + self._headroom())
            self._kv.ensure_rows(i, upto)
            if self._dspec:
                # the draft chain writes the same rows this round (its
                # append rides the identical dev_lengths), so it grows in
                # lockstep from the admission-time draft reservation
                self._kv.ensure_draft_rows(i, upto)

    # --------------------------------------------------- pipelined dispatch
    def _dispatch(self, adm_active=False):
        """Dispatch the next decode step WITHOUT waiting for the previous
        one (still undrained — ``_step_impl`` holds its record).  The
        step's inputs are all device-resident: the carried ``cur`` tokens /
        lengths of the previous dispatch (still futures — the device
        executes in program order) plus the caches; slots admitted since
        the last dispatch mix their host-known first token and prompt
        length into the carry.  A slot whose FINAL prefill chunk was just
        dispatched joins with its DEVICE-resident first token
        (``_dev_first`` — still a future) and host-known prompt length;
        its first token rides this record and is emitted at its drain."""
        live = [i for i in range(self._B) if self._decodable(i)]
        if not live:
            return
        self._ensure_decode_rows(live)
        with self._phase("dispatch", **self._dispatch_detail(live)):
            self._dispatch_live(live, adm_active)

    def _dispatch_detail(self, live):
        """What a ``dispatch`` phase says of itself: the batch it runs
        over and the engine's storage / kernel knobs."""
        return dict(mode=self._mode, n_live=len(live), kv_quant=self._kvq,
                    attn_impl=self._attn_label,
                    prefill_impl=self._prefill_label,
                    weight_dtype=self._wq_label)

    def _dispatch_live(self, live, adm_active):
        m = self._m
        active = np.array([self._decodable(i) for i in range(self._B)])
        host_len = self._decode_lengths(active)
        use_host = ~active
        use_host[list(self._adm_pending)] = True
        # freshly prefilled slots: length is host-known (the prompt length,
        # stamped at the final chunk) but cur is a device future
        use_host_len = use_host.copy()
        use_host_len[list(self._dev_first)] = True
        if self._dev_cur is None:
            cur = jnp.asarray(self._cur.copy())
        else:
            cur = jnp.where(jnp.asarray(use_host), jnp.asarray(self._cur.copy()),
                            self._dev_cur)
        for s, f in self._dev_first.items():
            cur = cur.at[s].set(f[0])
        self._dev_first.clear()
        firsts, self._pending_firsts = self._pending_firsts, []
        if self._mode == "greedy":
            # greedy lengths are host-derivable: every live slot advances
            # exactly sync_every per dispatch, so the mirror (bumped below)
            # IS the device value and needs no device carry
            def go(attempt):
                self._fault_point("dispatch", attempt)
                return self._call_decode(cur, host_len)
            toks, okd, self._kv.caches, *routes = self._retry(
                go, "decode dispatch")
            self._dev_cur = toks[:, -1]
            for i in live:
                self._kv.lengths[i] += self._sync
            self._inflight = {"kind": "greedy", "toks": toks, "ok": okd,
                              "reqs": list(self._kv.reqs), "live": live,
                              "firsts": firsts, "adm": adm_active}
            if routes:
                self._inflight["routes"] = routes[0]
                self._inflight["chunk_routes"], self._chunk_routes = \
                    self._chunk_routes, []
        else:
            if self._dev_len is None:
                dev_len = host_len
            else:
                # spec lengths advance by the DEVICE-known j+1, so the
                # carry comes back from serving_spec_step; host values are
                # authoritative only for just-admitted / just-prefilled
                # (prompt length) and freed (masked to lmax) slots
                dev_len = jnp.where(jnp.asarray(use_host_len), host_len,
                                    self._dev_len)

            k = self._next_k(live)
            self._event("draft", source=self._spec.source, k=k,
                        n_live=len(live))

            def go(attempt):
                self._fault_point("dispatch", attempt)
                return self._call_spec(cur, dev_len, jnp.asarray(active),
                                       k)
            blk, j, cur2, new_len, oks, self._kv.caches, self._hist, \
                self._hist_len = self._retry(go, "spec dispatch")
            self._dev_cur, self._dev_len = cur2, new_len
            self._inflight = {"kind": "spec", "blk": blk, "j": j,
                              "ok": oks, "k": k,
                              "reqs": list(self._kv.reqs), "live": live,
                              "firsts": firsts, "adm": adm_active}
        self._adm_pending.clear()
        if m is not None:
            m.inflight.set(1)

    def _drain(self, rec):
        """Sync the PREVIOUS iteration's dispatch (handed over by
        ``_step_impl`` after the next one is already issued) and run the
        host-side emit / retire bookkeeping for it.  A slot whose Request
        object changed since that dispatch (retired, or
        retired-and-readmitted) gets its stale tokens discarded — the
        host-visible half of the one-step-late retirement invariant."""
        if rec is None:
            return 0
        with self._phase("drain", mode=rec["kind"], n_live=len(rec["live"])):
            return self._drain_record(rec)

    def _drain_record(self, rec):
        m = self._m
        # the freshly issued dispatch (if any) stays outstanding through
        # this drain — that overlap is the point; the gauge must not claim
        # the pipe is empty just because THIS record got synced
        still_inflight = 1 if self._inflight is not None else 0
        firsts = rec.get("firsts", [])
        spec = rec["kind"] != "greedy"
        fo = [x for _, _, f, o in firsts for x in (f, o)]
        out = (rec["blk"], rec["j"], rec["ok"]) if spec \
            else (rec["toks"], rec["ok"])
        # recorded routes ride the same fetch (no extra device sync)
        routed = [rec["routes"]] + [c[2] for c in rec["chunk_routes"]] \
            if "routes" in rec else []
        # the ONE blocking fetch of the iteration: what the engine thread
        # waits on the device for (serving_pipeline_stall_seconds)
        with self._phase("drain.wait",
                         observe=m.pipeline_stall if m is not None else None):
            vals = self._fetch("drain", *out, *fo, *routed)
        if m is not None:
            m.inflight.set(still_inflight)
        n = len(out) + len(fo)
        with self._phase("emit"):
            if routed:
                self._take_routes(rec, vals[n], vals[n + 1:])
            return self._emit_record(rec, vals[:len(out)], vals[len(out):n])

    def _take_routes(self, rec, decode, chunks):
        """A drained record's recorded routes (``int8``, ``-1`` where a row
        was not live): the prefill runs dispatched ahead of this decode
        dispatch (``[rows, L_moe, k]`` each) go to their requests'
        ``routes`` row for row, the dispatch's own
        ``[B, n_steps, L_moe, k]`` are kept on
        the record for ``_emit_record`` to hand out with the tokens; both
        feed the expert counters (one ``bincount`` a program)."""
        m = self._m
        for (r, n, _, admission), routes in zip(rec["chunk_routes"], chunks):
            if r.preempts != admission:
                continue        # parked since: re-admission records anew
            if r.routes is None:
                r.routes = []
            r.routes.append(routes[:n])
            if m is not None:
                m.expert_routes("prefill", routes[:n, None], self._n_experts)
        rec["routes"] = decode
        if m is not None:
            m.expert_routes("decode", decode, self._n_experts)

    def _emit_record(self, rec, out, fvals):
        """Hand a drained record's tokens to their requests."""
        m = self._m
        emitted = 0
        if rec["kind"] == "greedy":
            self._observe_interference(rec.get("adm", False), self._sync)
        # the first tokens ride the record they were dispatched before
        # (program order: final prefill chunk, then this decode step) —
        # emit them ahead of the slot's decode block
        for n, (slot, r, _, _) in enumerate(rec.get("firsts", [])):
            if self._kv.reqs[slot] is not r:
                continue
            fv, ov = fvals[2 * n], fvals[2 * n + 1]
            if not bool(ov[0]):
                self._retire(slot, "poisoned")
                continue
            if self._paged:
                # post-finite-check, pre-_emit (which may release):
                # same registration rule as _flush_firsts
                self._kv.register_prefix(slot, r._adm_ids)
                if self._dspec:
                    self._kv.register_draft_prefix(slot, r._adm_ids)
            self._cur[slot] = int(fv[0])
            emitted += self._emit(slot, [int(fv[0])])
        if rec["kind"] == "greedy":
            toks, okd = out
            for i in rec["live"]:
                if self._kv.reqs[i] is not rec["reqs"][i]:
                    continue
                if not bool(okd[i]):
                    self._retire(i, "poisoned")
                    continue
                took = self._emit(i, toks[i].tolist())
                emitted += took
                self._cur[i] = toks[i, -1]
                if "routes" in rec and took:
                    # the routes of each emitted token's INPUT position
                    rec["reqs"][i].routes.append(rec["routes"][i, :took])
            return emitted
        blk, j, okd = out
        k = rec.get("k", self._spec_k)
        accepted = 0
        drained = 0
        rounds = []
        for i in rec["live"]:
            if self._kv.reqs[i] is not rec["reqs"][i]:
                continue
            if not bool(okd[i]):
                self._retire(i, "poisoned")
                continue
            drained += 1
            emitted += self._emit(i, blk[i, :int(j[i]) + 1].tolist())
            self._kv.lengths[i] += int(j[i]) + 1
            accepted += int(j[i])
            rounds.append((i, int(j[i])))
        self._event("verify", k=k, drafted=k * drained, accepted=accepted)
        self._event("rewind", tokens=k * drained - accepted)
        self._adapt_k(rounds, k)
        self._observe_interference(
            rec.get("adm", False), 1.0 + accepted / max(1, drained))
        if m is not None and drained:
            m.spec_round(k * drained, accepted)
        return emitted

    def run(self):
        """Drive ``step()`` until the queue and every slot drain; returns
        the finished requests in completion order."""
        while self.has_work:
            self.step()
        return self._finished

    def drain(self):
        """Run the engine to quiescence, then return ``{rid: terminal
        status}`` over every request it finished — the graceful-shutdown
        half of ``close()`` (all outstanding work completes; deadlines
        and faults still apply while draining)."""
        self.run()
        return {r.rid: r.status for r in self._finished}

    def close(self):
        """Abort outstanding work cleanly.  The inflight pipelined
        dispatch (if any) is drained first — its tokens still emit, so
        every in-flight request keeps its partial output — then every
        queued and resident request is retired with terminal status
        ``"cancelled"``.  Returns ``{rid: terminal status}`` over every
        request the engine ever finished.  Idempotent: a second call
        finds nothing to cancel and returns the same map."""
        if self._watchdog is not None:
            self._watchdog.stop()
        if self._inflight is not None:
            prev, self._inflight = self._inflight, None
            self._drain(prev)
        while self._queue:
            self._terminal_queued(self._queue.popleft(), "cancelled")
        for slot in range(self._B):
            if self._kv.reqs[slot] is not None:
                self._retire(slot, "cancelled")
        if self._m is not None:
            self._m.queue_depth.set(len(self._queue))
        return {r.rid: r.status for r in self._finished}

    # ------------------------------------------------- fleet introspection
    # the surface serving/replica.py programs against: pure host reads
    # (no device work, no allocation) a router can poll every route
    @property
    def kv_block(self):
        """Paged KV block size in tokens (None on dense engines) — the
        chunk width router-side prefix mirrors must key on."""
        return self._kv.block if self._paged else None

    def queue_depth(self):
        """Requests waiting for a slot (the admission backlog)."""
        return len(self._queue)

    def prefix_lookup(self, tokens):
        """Longest cached prefix (in tokens) this engine holds for
        ``tokens`` across BOTH tiers — the device radix match plus its
        contiguous host-tier continuation (a restore at admission makes
        those tokens just as reusable) — the router's cache-aware
        placement probe.  Pure probe: no LRU heat on either tier.  0 on
        dense engines."""
        if not self._paged:
            return 0
        tok = np.asarray(tokens, np.int32).reshape(-1)
        matched, _ = self._kv.match_prefix(tok, touch=False)
        if self._kv.host_tier is not None:
            matched += self._kv.host_match(tok, matched)
        return int(matched)

    def stats(self):
        """JSON-ready scheduling snapshot for replica handles/routers:
        backlog, occupancy, and the cumulative paged prompt/reuse and
        preemption token tallies.  Maintained unconditionally, so
        ``instrument=False`` engines report them too."""
        return {
            "queue_depth": len(self._queue),
            "slots_occupied": self._kv.occupied(),
            "slots_total": self._B,
            "prefill_slots": len(self._pf),
            "inflight": 1 if self._inflight is not None else 0,
            "live_tokens": int(self._kv.live_tokens()),
            "prompt_tokens": self._n_prompt_tokens,
            "prefix_reuse_tokens": self._n_reuse_tokens,
            "host_reuse_tokens": self._n_host_reuse_tokens,
            "preempted": self._n_preempted,
            "preempt_resume_suffix_tokens": self._n_resume_suffix,
            "preempt_resume_total_tokens": self._n_resume_total,
        }

    @property
    def kv_manager(self):
        """The engine's KV cache manager — the paged block-pool surface
        ``serving/disagg.py`` exports/imports block chains through
        (``block_chain`` / ``export_chain``)."""
        return self._kv

    # ------------------------------------------------- debug introspection
    @property
    def recorder(self):
        """The engine's ``FlightRecorder`` (None when ``recorder=False``)."""
        return self._fr

    @property
    def slo_tracker(self):
        """The engine's ``SLOTracker``."""
        return self._slo

    def requests_snapshot(self, last=64):
        """JSON-ready view of the most recent request timelines (newest
        ``last`` of the rid-keyed trace cache, including still-live
        requests).  Thread-safe: copies under the trace lock, so a scrape
        thread can call it mid-``step()``."""
        with self._trace_lock:
            traces = list(self._traces.values())[-int(last):]
        return {
            "n_tracked": len(traces),
            "requests": [{"rid": t.rid, "phase": t.phase,
                          "timeline": t.as_dicts()} for t in traces],
        }

    def recorder_snapshot(self, last=256):
        """JSON-ready flight-recorder view (plus the fault plan, when one
        is configured, so a postmortem reader sees the injected schedule
        next to the events it caused)."""
        if self._fr is None:
            return {"enabled": False}
        snap = self._fr.snapshot(last=last)
        snap["enabled"] = True
        if self._faults is not None:
            snap["fault_plan"] = self._faults.snapshot()
        return snap

    def slo_snapshot(self):
        """JSON-ready windowed SLO attainment / burn-rate view."""
        return self._slo.snapshot()

    def debug_sources(self):
        """``{name: callable}`` map for ``MetricsExporter`` — wires the
        engine's ``/debug/requests``, ``/debug/flightrecorder`` and
        ``/debug/slo`` endpoints in one call::

            MetricsExporter(debug_sources=engine.debug_sources()).start()
        """
        return {"requests": self.requests_snapshot,
                "flightrecorder": self.recorder_snapshot,
                "slo": self.slo_snapshot}
