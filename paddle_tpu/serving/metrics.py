"""Pre-bound observability series for one serving engine.

Extracted from serving/engine.py alongside the KVCacheManager so the
engine file holds scheduling logic only.  The series live in ``registry``
(default: the process-wide one) keyed by a ``policy`` label, which the
engine sets to its one scheduling policy, ``"continuous"``.
All instrumentation is host-side bookkeeping — the compiled device
programs are untouched, which is what keeps the instrumented engine's
token outputs byte-identical to an uninstrumented run (tested:
tests/test_observability.py).
"""
from __future__ import annotations

import numpy as np

from paddle_tpu.observability.metrics import get_registry

__all__ = ["EngineMetrics", "DisaggMetrics"]


class EngineMetrics:
    """One engine's metric children, bound once at construction."""

    def __init__(self, registry, policy, batch_size, mesh_devices=1):
        reg = registry if registry is not None else get_registry()
        self.registry = reg
        L = ("policy",)
        lbl = {"policy": policy}
        self.mesh_devices = reg.gauge(
            "serving_mesh_devices",
            "devices the engine's compiled programs span (1 = single-chip)",
            L).labels(**lbl)
        self.mesh_devices.set(mesh_devices)
        self.queue_depth = reg.gauge(
            "serving_queue_depth", "requests waiting for a slot",
            L).labels(**lbl)
        self.slots_occupied = reg.gauge(
            "serving_slots_occupied", "batch slots holding a live request",
            L).labels(**lbl)
        self.slots_total = reg.gauge(
            "serving_slots_total", "engine batch size", L).labels(**lbl)
        self.slots_total.set(batch_size)
        self.admitted = reg.counter(
            "serving_requests_admitted_total",
            "requests admitted into a slot", L).labels(**lbl)
        self.retired = reg.counter(
            "serving_requests_retired_total",
            "requests completed (EOS or max_new_tokens)", L).labels(**lbl)
        self.emitted = reg.counter(
            "serving_tokens_emitted_total",
            "tokens delivered to requests", L).labels(**lbl)
        self.steps = reg.counter(
            "serving_steps_total", "scheduler iterations", L).labels(**lbl)
        self._prefills = reg.counter(
            "serving_prefill_total", "slot prefills by prompt bucket",
            ("policy", "bucket"))
        self._policy = policy
        self.queue_wait = reg.histogram(
            "serving_queue_wait_seconds",
            "submit -> slot admission", L).labels(**lbl)
        self.ttft = reg.histogram(
            "serving_ttft_seconds", "submit -> first token", L).labels(**lbl)
        self.tpot = reg.histogram(
            "serving_tpot_seconds",
            "mean per-token time after the first", L).labels(**lbl)
        self.e2e = reg.histogram(
            "serving_e2e_seconds", "submit -> completion", L).labels(**lbl)
        # request-lifecycle phase histograms, fed from the RequestTrace at
        # retirement: the three legs of queued -> prefilling -> decoding ->
        # terminal (TTFT/TPOT above already cover the composite views)
        self.queue_seconds = reg.histogram(
            "serving_queue_seconds",
            "lifecycle phase: submit -> slot admission (RequestTrace)",
            L).labels(**lbl)
        self.prefill_seconds = reg.histogram(
            "serving_prefill_seconds",
            "lifecycle phase: slot admission -> first token (RequestTrace)",
            L).labels(**lbl)
        self.decode_seconds = reg.histogram(
            "serving_decode_seconds",
            "lifecycle phase: first token -> terminal status (RequestTrace)",
            L).labels(**lbl)
        # anomaly auto-dumps of the flight recorder, by trigger; every
        # reason child is pre-registered so a first scrape before any
        # anomaly shows the full zero-valued series set
        self._recorder_dumps = reg.counter(
            "flight_recorder_dumps_total",
            "anomaly-triggered flight-recorder snapshots, by trigger",
            ("policy", "reason"))
        for reason in ("timed_out", "poisoned", "retry_exhausted",
                       "stall"):
            self._recorder_dumps.labels(policy=policy, reason=reason)
        # wall-clock stamp of the most recent scheduler step: /healthz
        # derives "last-step age" from it, so a wedged engine (stuck
        # dispatch, dead loop) is visible to a router's health check
        # without parsing the full /metrics page
        self.last_step_time = reg.gauge(
            "serving_last_step_unixtime",
            "time.time() of the engine's most recent scheduler step "
            "(0 until the first step)", L).labels(**lbl)
        # keyed by exception type so a scrape distinguishes a buggy user
        # callback (TypeError) from an injected crash; the bare series is
        # pre-registered under error="Exception" so the family exports
        # zero-valued before the first crash
        self._stream_cb_errors = reg.counter(
            "serving_stream_cb_errors_total",
            "stream_cb exceptions swallowed by the scheduler, by "
            "exception type", ("policy", "error"))
        self._stream_cb_errors.labels(policy=policy, error="Exception")
        # reliability counters (pre-bound here so a Prometheus scrape sees
        # zero-valued series before the first shed/timeout/cancel/poison —
        # the registry convention every other engine series follows)
        self.shed = reg.counter(
            "serving_requests_shed_total",
            "requests rejected at submit() by the bounded admission "
            "queue (load shedding)", L).labels(**lbl)
        self.timed_out = reg.counter(
            "serving_requests_timed_out_total",
            "requests retired by deadline_ms expiry", L).labels(**lbl)
        self.cancelled = reg.counter(
            "serving_requests_cancelled_total",
            "requests retired by host-side cancel()/close()",
            L).labels(**lbl)
        self.poisoned = reg.counter(
            "serving_requests_poisoned_total",
            "requests quarantined after non-finite logits",
            L).labels(**lbl)
        self.dispatch_retries = reg.counter(
            "serving_dispatch_retries_total",
            "transient dispatch/drain failures retried with backoff",
            L).labels(**lbl)
        self.spec_drafted = reg.counter(
            "serving_spec_drafted_total",
            "draft tokens proposed per speculative round", L).labels(**lbl)
        self.spec_accepted = reg.counter(
            "serving_spec_accepted_total",
            "draft tokens accepted by the verify forward", L).labels(**lbl)
        # speculative drafting series, source-labeled: the accept-rate
        # gauge is keyed by the DRAFT SOURCE (prompt_lookup = n-gram
        # history mining, draft_model = the resident shrunk-llama
        # drafter) so an A/B scrape separates the two policies; both
        # children pre-registered, the engine points ``set_spec_source``
        # at its active one.  ``spec_draft_k`` tracks the depth actually
        # in effect — it MOVES under the adaptive-k ladder
        self.spec_accept_rate = reg.gauge(
            "serving_spec_accept_rate",
            "cumulative accepted/drafted ratio, by draft source",
            ("policy", "source"))
        for source in ("prompt_lookup", "draft_model"):
            self.spec_accept_rate.labels(policy=policy, source=source)
        self._spec_source = "prompt_lookup"
        self._spec_draft_source = reg.gauge(
            "serving_spec_draft_source",
            "draft-source info gauge: the child whose source label names "
            "the engine's drafting policy reads 1, the other "
            "pre-registered child 0", ("policy", "source"))
        for source in ("prompt_lookup", "draft_model"):
            self._spec_draft_source.labels(policy=policy, source=source) \
                .set(0)
        self.spec_draft_k = reg.gauge(
            "serving_spec_draft_k",
            "draft tokens per speculative round currently in effect "
            "(moves under the adaptive-k policy; fixed-k engines hold "
            "the constructor knob)", L).labels(**lbl)
        self.prefill_chunks = reg.counter(
            "serving_prefill_chunks_total",
            "prompt chunks (prefill_chunk rows each) spent by the "
            "chunked-prefill path", L).labels(**lbl)
        self.prefill_runs = reg.counter(
            "serving_prefill_runs_total",
            "runs of the prefill program, of any width: chunks / runs is "
            "how many chunks one read of the weights served",
            L).labels(**lbl)
        # recurrent state beside the K/V rows (a serving family's
        # state_leaves; 0 and never bumped for a model that has none)
        self.state_bytes = reg.gauge(
            "serving_state_bytes",
            "bytes of per-slot recurrent state (SSM state, conv tails) "
            "resident beside the K/V cache", L).labels(**lbl)
        self.state_resets = reg.counter(
            "serving_state_resets_total",
            "slot admissions whose first prefill chunk zeroed the slot's "
            "recurrent state", L).labels(**lbl)
        # the decode state update against the slots it skips (one layer's
        # count at each decode dispatch's host lengths, by the update's own
        # rule: ops.ssm.ssm_state_update); skipped / (read + skipped) is
        # the share of the whole batch's state bytes not moved
        self.state_slots_read = reg.counter(
            "serving_state_slots_read_total",
            "slots whose recurrent state one layer's decode update reads, "
            "summed over decode dispatches and steps", L).labels(**lbl)
        self.state_slots_skipped = reg.counter(
            "serving_state_slots_skipped_total",
            "parked slots one layer's decode update skips, summed over "
            "decode dispatches and steps", L).labels(**lbl)
        # the decode cache read against the rows it needs (one layer's
        # count at each decode dispatch's host lengths, by the read's own
        # rule: ops.decode_attention.kv_rows_read); read / live is the
        # over-read
        self.kv_rows_read = reg.counter(
            "serving_kv_rows_read_total",
            "cache rows one layer's decode read touches, summed over "
            "decode dispatches", L).labels(**lbl)
        self.kv_rows_live = reg.counter(
            "serving_kv_rows_live_total",
            "cache rows some live slot attends to, summed over decode "
            "dispatches", L).labels(**lbl)
        # routed experts (a serving family's routed_experts; never bumped
        # for a model that has none), fed on the host from the routes a
        # dispatch or a prefill chunk hands back with its tokens
        self._expert_tokens = reg.counter(
            "serving_moe_expert_tokens_total",
            "live (token, expert) pairs served, all expert layers",
            ("policy", "expert"))
        self._experts_touched = reg.counter(
            "serving_moe_experts_touched_total",
            "experts with at least one live pair, summed over expert "
            "layers, steps and runs of a program", ("policy", "program"))
        self._moe_dispatches = reg.counter(
            "serving_moe_dispatches_total",
            "runs of a program whose recorded routes were counted",
            ("policy", "program"))
        # the children, looked up once: expert_routes runs on the scheduler
        # thread at every dispatch and every chunk
        self._expert_children = {}
        self._experts_touched, self._moe_dispatches = (
            {program: c.labels(policy=policy, program=program)
             for program in ("decode", "prefill")}
            for c in (self._experts_touched, self._moe_dispatches))
        self.prefill_backlog = reg.gauge(
            "serving_prefill_backlog",
            "prompt chunks still to spend across slots mid-prefill",
            L).labels(**lbl)
        self.tpot_admission = reg.histogram(
            "serving_tpot_during_admission_seconds",
            "per-token decode interval observed while a chunked prefill "
            "was in progress — the decode-interference histogram",
            L).labels(**lbl)
        self.pipeline_stall = reg.histogram(
            "serving_pipeline_stall_seconds",
            "drain-side block waiting on the inflight dispatch",
            L).labels(**lbl)
        self.inflight = reg.gauge(
            "serving_inflight_steps",
            "device steps dispatched but not yet drained", L).labels(**lbl)
        # paged-KV series (PagedKVCacheManager): block-pool occupancy,
        # the token-budget admission numerator, and the prefix-reuse
        # counters a prefix hit rate derives from (reuse / prompt
        # tokens).  Zero-valued on dense engines —
        # pre-registered like every other family
        # pool occupancy is TENANT-split: target = the served model's
        # chains plus evictable cached prefixes, draft = the resident
        # draft model's live chains (freed outright at refcount 0, so
        # the draft child returns to 0 after drain — the tenancy
        # accounting invariant tests pin)
        self.kv_blocks_used = reg.gauge(
            "serving_kv_blocks_used",
            "KV pool blocks live or holding an evictable cached prefix, "
            "by tenant model", ("policy", "model"))
        for model in ("target", "draft"):
            self.kv_blocks_used.labels(policy=policy, model=model)
        self.kv_blocks_free = reg.gauge(
            "serving_kv_blocks_free",
            "KV pool blocks on the free list", L).labels(**lbl)
        self.live_tokens = reg.gauge(
            "serving_live_tokens",
            "context tokens held by live slots (token-budget admission "
            "numerator; dense strands batch*max_len minus this)",
            L).labels(**lbl)
        self.prefix_reuse_tokens = reg.counter(
            "serving_prefix_reuse_tokens_total",
            "prompt tokens satisfied from cached prefix blocks instead "
            "of being prefilled", L).labels(**lbl)
        self.prompt_tokens = reg.counter(
            "serving_prompt_tokens_total",
            "prompt tokens admitted on the paged path (prefix hit-rate "
            "denominator)", L).labels(**lbl)
        # priority preemption (paged engines): parks, and the suffix
        # tokens the resumes actually re-prefilled — the recompute cost
        # the EVICTABLE park keeps small
        self.preempted = reg.counter(
            "serving_preempted_total",
            "resident requests parked by priority preemption (blocks "
            "released EVICTABLE — the radix chain survives for the "
            "suffix-cost resume)", L).labels(**lbl)
        self.preempt_resume_tokens = reg.counter(
            "serving_preempt_resume_tokens_total",
            "suffix tokens prefilled when preempted requests resumed "
            "(the adopted prefix rows were free — this counter IS the "
            "preemption recompute cost)", L).labels(**lbl)
        # tiered KV cache (host_tier_bytes=): host-store occupancy
        # gauges, tier-labeled hit counters (every tier child
        # pre-registered so a first scrape shows the full zero-valued
        # set), demotion/restore volumes, validation failures, and the
        # restore latency the restore-vs-reprefill crossover reads
        self.kv_host_blocks = reg.gauge(
            "serving_kv_host_blocks",
            "KV blocks resident in the host-RAM demotion tier",
            L).labels(**lbl)
        self.kv_host_bytes = reg.gauge(
            "serving_kv_host_bytes",
            "bytes resident in the host-RAM demotion tier (its LRU "
            "evicts at the host_tier_bytes budget)", L).labels(**lbl)
        self._prefix_hits = reg.counter(
            "serving_prefix_hits_total",
            "admissions that adopted a cached prefix, by serving tier "
            "(device = radix blocks already in the pool, host = blocks "
            "restored from the host tier, fleet = chains imported from "
            "another engine)", ("policy", "tier"))
        for tier in ("device", "host", "fleet"):
            self._prefix_hits.labels(policy=policy, tier=tier)
        self.tier_demotions = reg.counter(
            "serving_tier_demotions_total",
            "KV blocks demoted (evicted device chain copied into the "
            "host tier off the step path)", L).labels(**lbl)
        self.tier_restores = reg.counter(
            "serving_tier_restores_total",
            "KV blocks restored from the host tier at admission (a "
            "kv_transfer device_put, not a suffix prefill)",
            L).labels(**lbl)
        self.host_tier_errors = reg.counter(
            "serving_host_tier_errors_total",
            "host-tier entries dropped by restore-time validation "
            "(structure or CRC mismatch) — admission fell back to "
            "suffix prefill instead of splicing wrong bytes",
            L).labels(**lbl)
        self.tier_restore_seconds = reg.histogram(
            "serving_tier_restore_seconds",
            "admission-side wall time of one host-tier chain restore "
            "(fetch + validate + device scatter)", L).labels(**lbl)
        # KV quantization (kv_dtype=): an INFO gauge — one child per
        # known mode, the active one reads 1 — so a scrape (and
        # /debug/flightrecorder's kv_quant dispatch detail) states the
        # storage mode without string-valued metrics, plus the analytic
        # per-context-token KV traffic at int8 (0 on unquantized
        # engines; ~0.53x the bf16 figure)
        self._kv_quant_mode = reg.gauge(
            "serving_kv_quant_mode",
            "KV cache quantization mode info gauge: the child whose "
            "mode label names the active storage scheme reads 1, every "
            "other pre-registered child 0", ("policy", "mode"))
        for mode in ("off", "int8"):
            self._kv_quant_mode.labels(policy=policy, mode=mode).set(0)
        self.hbm_gb_per_tok_q8 = reg.gauge(
            "serving_hbm_gb_per_tok_q8",
            "analytic KV bytes (GB) read per context token at int8 "
            "storage: layers * 2 * Hkv * (D + 2 scale bytes); zero when "
            "kv_dtype is unquantized", L).labels(**lbl)
        # decode-kernel selection (attn_impl=) and weight quantization
        # (weight_dtype=): the same info-gauge shape as kv_quant_mode —
        # every known child pre-registered to 0 so a scrape always shows
        # the full mode set, the active child set to 1 at construction —
        # plus the analytic int8-weight traffic figure
        self._decode_kernel = reg.gauge(
            "serving_decode_kernel",
            "decode cache-read implementation info gauge: 'fused' (the "
            "Pallas gather+dequant+softmax kernel) or 'reference' (the "
            "chunked lax.while_loop); the active child reads 1",
            ("policy", "impl"))
        for impl in ("reference", "fused"):
            self._decode_kernel.labels(policy=policy, impl=impl).set(0)
        # prefill-kernel selection (prefill_impl=) mirrors the decode
        # info gauge, and tp_overlap is a plain valued gauge — the
        # segment count itself (0 = single fused matmul, no overlap)
        self._prefill_kernel = reg.gauge(
            "serving_prefill_kernel",
            "chunked-prefill implementation info gauge: 'fused' (the "
            "Pallas prefill+append kernel) or 'reference' (the dense "
            "fold + scatter append); the active child reads 1",
            ("policy", "impl"))
        for impl in ("reference", "fused"):
            self._prefill_kernel.labels(policy=policy, impl=impl).set(0)
        self._tp_overlap_mode = reg.gauge(
            "serving_tp_overlap_mode",
            "row-parallel TP overlap segment count: 0 when the "
            "per-layer psum runs as one fused reduction, N>=2 when the "
            "wo/down matmuls are split into N output-feature segments "
            "so each segment's collective overlaps the next matmul",
            L).labels(**lbl)
        self._weight_quant_mode = reg.gauge(
            "serving_weight_quant_mode",
            "decode matmul weight quantization mode info gauge: the "
            "child whose mode label names the active storage scheme "
            "reads 1, every other pre-registered child 0",
            ("policy", "mode"))
        for mode in ("off", "int8"):
            self._weight_quant_mode.labels(policy=policy, mode=mode).set(0)
        self.hbm_gb_per_tok_w8 = reg.gauge(
            "serving_hbm_gb_per_tok_w8",
            "analytic decode-weight bytes (GB) read per generated token "
            "at int8 storage: every projection element once (1 byte) + "
            "2 f16 scale bytes per output channel; zero when "
            "weight_dtype is unquantized", L).labels(**lbl)

    def prefill(self, bucket):
        self._prefills.labels(policy=self._policy, bucket=bucket).inc()

    def prefix_hit(self, tier):
        """Count one prefix-adopting admission against ``tier``
        ('device' | 'host' | 'fleet')."""
        self._prefix_hits.labels(policy=self._policy, tier=tier).inc()

    def set_kv_quant(self, mode):
        """Point the kv-quant info gauge at ``mode`` (exactly one child
        reads 1 after this — the engine calls it once at construction)."""
        for m in ("off", "int8"):
            self._kv_quant_mode.labels(policy=self._policy, mode=m).set(
                1 if m == mode else 0)

    def set_decode_kernel(self, impl):
        """Point the decode-kernel info gauge at ``impl`` ('reference' or
        'fused') — the engine calls it once at construction."""
        for i in ("reference", "fused"):
            self._decode_kernel.labels(policy=self._policy, impl=i).set(
                1 if i == impl else 0)

    def set_prefill_kernel(self, impl):
        """Point the prefill-kernel info gauge at ``impl`` ('reference'
        or 'fused') — the engine calls it once at construction."""
        for i in ("reference", "fused"):
            self._prefill_kernel.labels(policy=self._policy, impl=i).set(
                1 if i == impl else 0)

    def set_tp_overlap(self, segments):
        """Record the TP-overlap segment count (0 = overlap off)."""
        self._tp_overlap_mode.set(int(segments))

    def set_weight_quant(self, mode):
        """Point the weight-quant info gauge at ``mode`` ('off' or
        'int8') — the engine calls it once at construction."""
        for m in ("off", "int8"):
            self._weight_quant_mode.labels(policy=self._policy, mode=m).set(
                1 if m == mode else 0)

    def expert_routes(self, program, routes, n_experts):
        """Count one run's recorded routes ``int8 [rows, steps, L_moe, k]``
        (``-1``: the row was not live): pairs by expert, and experts
        touched a (step, layer)."""
        live = routes >= 0
        flat = routes[live]
        self._moe_dispatches[program].inc()
        if not flat.size:
            return
        counts = np.bincount(flat, minlength=n_experts)
        for e in np.flatnonzero(counts):
            child = self._expert_children.get(e)
            if child is None:
                child = self._expert_children[e] = self._expert_tokens.labels(
                    policy=self._policy, expert=str(e))
            child.inc(int(counts[e]))
        # distinct (step, layer, expert) triples among the live pairs
        steps, layers = np.nonzero(live)[1:3]
        key = (steps * routes.shape[2] + layers) * n_experts + flat
        self._experts_touched[program].inc(
            int(np.count_nonzero(np.bincount(key))))

    def stream_cb_error(self, etype):
        self._stream_cb_errors.labels(
            policy=self._policy, error=etype).inc()

    def recorder_dump(self, reason):
        """Count one anomaly auto-dump (FlightRecorder ``on_dump`` hook)."""
        self._recorder_dumps.labels(
            policy=self._policy, reason=reason).inc()

    def observe_phases(self, durations):
        """Feed the lifecycle phase histograms from a RequestTrace's
        ``durations()`` dict (absent legs are skipped — a shed request
        has no decode phase to observe)."""
        v = durations.get("queue")
        if v is not None:
            self.queue_seconds.observe(v)
        v = durations.get("prefill")
        if v is not None:
            self.prefill_seconds.observe(v)
        v = durations.get("decode")
        if v is not None:
            self.decode_seconds.observe(v)

    def terminal(self, status):
        """Bump the reliability counter for a non-``done`` terminal
        status (the ``done`` path keeps its dedicated ``retired``
        counter)."""
        c = {"shed": self.shed, "timed_out": self.timed_out,
             "cancelled": self.cancelled,
             "poisoned": self.poisoned}.get(status)
        if c is not None:
            c.inc()

    def set_spec_source(self, source):
        """Point the draft-source info gauge at ``source`` and route
        subsequent ``spec_round`` accept-rate updates to that child —
        the engine calls it once at construction."""
        self._spec_source = source
        for s in ("prompt_lookup", "draft_model"):
            self._spec_draft_source.labels(
                policy=self._policy, source=s).set(1 if s == source else 0)

    def set_kv_blocks(self, target_used, draft_used, free):
        """Post the tenant-split pool occupancy in one call (the
        engine's ``_kv_event`` hook)."""
        self.kv_blocks_used.labels(
            policy=self._policy, model="target").set(target_used)
        self.kv_blocks_used.labels(
            policy=self._policy, model="draft").set(draft_used)
        self.kv_blocks_free.set(free)

    def spec_round(self, drafted, accepted):
        self.spec_drafted.inc(drafted)
        self.spec_accepted.inc(accepted)
        total = self.spec_drafted.value
        if total:
            self.spec_accept_rate.labels(
                policy=self._policy, source=self._spec_source).set(
                self.spec_accepted.value / total)


class DisaggMetrics:
    """One DisaggCoordinator's migration series (serving/disagg.py),
    keyed by the coordinator's ``name`` label — a fleet of disagg cells
    stays separable in one scrape.  Every series (and every known label
    child) is pre-registered at construction, the registry convention:
    a scrape before the first migration shows the full zero-valued set."""

    def __init__(self, registry, name):
        reg = registry if registry is not None else get_registry()
        self.registry = reg
        L = ("coordinator",)
        lbl = {"coordinator": name}
        self.transfer_seconds = reg.histogram(
            "serving_kv_transfer_seconds",
            "one migration's KV handoff: block-chain export on the "
            "prefill pool through import into the decode pool",
            L).labels(**lbl)
        self.transfer_bytes = reg.counter(
            "serving_kv_transfer_bytes_total",
            "KV cache bytes shipped prefill -> decode (data + int8 "
            "scale leaves, every layer)", L).labels(**lbl)
        self._migrations = reg.counter(
            "serving_migrations_total",
            "prefill -> decode migrations by outcome: ok (spliced and "
            "decoding) or aborted (cancelled/expired before adoption)",
            ("coordinator", "outcome"))
        for outcome in ("ok", "aborted"):
            self._migrations.labels(coordinator=name, outcome=outcome)
        self.prefill_backlog = reg.gauge(
            "serving_prefill_worker_backlog",
            "requests queued or resident across the prefill workers",
            L).labels(**lbl)
        self.decode_backlog = reg.gauge(
            "serving_decode_worker_backlog",
            "requests resident across the decode workers plus "
            "migrations awaiting adoption", L).labels(**lbl)
        self.worker_restarts = reg.counter(
            "serving_worker_restarts_total",
            "worker processes respawned after a death was detected "
            "(fleet launcher / FaultPlan worker_kill)", L).labels(**lbl)
        self.orphan_reprefills = reg.counter(
            "serving_orphan_reprefills_total",
            "requests orphaned by a decode-worker death and resumed as "
            "a suffix prefill (prompt + emitted tokens)", L).labels(**lbl)
        self.overlap_stall = reg.histogram(
            "serving_kv_transfer_overlap_stall_seconds",
            "time a migration spent holding up an available decode slot "
            "because its chain bytes were still on the wire (0 = the "
            "transfer fully overlapped decode steps)", L).labels(**lbl)
        self._name = name

    def migration(self, outcome):
        self._migrations.labels(
            coordinator=self._name, outcome=outcome).inc()
