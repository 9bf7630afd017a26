"""Falcon-H1 (parallel Mamba-2 + attention in every block) against its plain
float32 reference (``benchmark/lib/falcon_h1_ref.py``), on seeded weights at
a tiny size: the model's forward, the state-space ops, the serving programs
through the cache, and the engine's handling of the recurrent state that
lives beside the K/V rows.

Tolerances.  Everything here is float32 at matmul precision "highest"
(``conftest.py``), so the program and the reference differ by summation
order alone: logits of O(1) agree to a few 1e-6 (measured 2.5e-6 for the
whole-sequence forward); ``LOGIT_TOL`` = 2e-4 leaves room for the chunked
scan's different association over a 40-token sequence and is 300 times
under the smallest logit gap a wrong state produces here (a skipped reset
reads 0.1-1).  The chunked scan against the token-by-token recurrence:
1e-5 relative on outputs of O(1).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import _wide_runs as W
import paddle_tpu as paddle
from benchmark.lib import falcon_h1_ref as ref
from benchmark.models import falcon_h1 as arch
from paddle_tpu.models import falcon_h1_decode as fd
from paddle_tpu.models.falcon_h1 import (
    FalconH1Config, FalconH1ForCausalLM, statics_of,
)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving_family import RowsLeaves, family_of
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.ops import ssm
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.serving.faults import FaultPlan

LOGIT_TOL = 2e-4
SEED = 2147483777
LMAX = 64


def tiny_config():
    c = FalconH1Config.tiny()
    config = {k: getattr(c, k) for k in c.__dataclass_fields__}
    config["torch_dtype"] = "float32"
    return config


@pytest.fixture(scope="module")
def config():
    return tiny_config()


@pytest.fixture(scope="module")
def model(config):
    m = arch.build(config, SEED, 128)
    m.eval()
    return m


def reference_logits(config, seq, rows):
    tokens = np.zeros((1, LMAX), np.int32)
    tokens[0, :len(seq)] = seq
    out, = arch.serve_logits(config, SEED, tokens,
                             np.asarray(rows, np.int32)[None])
    return out[0]


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).astype(np.int32) for n in lengths]


def engine(model, **kw):
    kw = dict(dict(batch_size=2, max_len=LMAX, prefill_chunk=16,
                   decode_chunk=16), **kw)
    return ServingEngine(model, **kw)


# (a) the Layer model's whole-sequence forward
def test_forward_is_the_reference(config, model):
    tok = np.stack(prompts((37, 37), seed=1))
    got = model(paddle.to_tensor(tok)).numpy()
    for row in range(2):
        want = reference_logits(config, tok[row], np.arange(37))
        np.testing.assert_allclose(got[row], want, atol=LOGIT_TOL, rtol=0)
    assert want.std() > 0.5          # the logits are O(1): the check bites


def test_parameters_are_born_in_their_dtype():
    """No float32 copy first: a fresh model's leaves are drawn straight
    into the configured dtype, each branch O(1) under the multipliers."""
    m = FalconH1ForCausalLM(FalconH1Config.tiny(dtype="bfloat16"))
    assert {str(p.dtype).split(".")[-1] for p in m.parameters()} \
        == {"bfloat16"}
    out = m(paddle.to_tensor(np.stack(prompts((24,))))).numpy()
    assert np.isfinite(out).all() and 0.3 < out.std() < 3.0


# (d) the ops against the token-by-token recurrence
def scan_operands(t, seed=0):
    g, e, p, n = 2, 4, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (t, g, e, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, g, e)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[2], (g, e)))
    bm, cm = (jax.random.normal(k, (t, g, n)) for k in ks[3:5])
    d = jnp.ones((g, e))
    s0 = jax.random.normal(ks[5], (g, e, p, n))
    return x, dt, a, bm, cm, d, s0


def recurrence(x, dt, a, bm, cm, d, s0):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t; y_t = S_t C_t + D x_t."""
    s, ys = np.asarray(s0, np.float64), []
    for t in range(x.shape[0]):
        keep = np.exp(np.asarray(dt[t] * a, np.float64))[..., None, None]
        add = np.einsum("gep,gn->gepn", np.asarray(dt[t][..., None] * x[t]),
                        np.asarray(bm[t]))
        s = keep * s + add
        ys.append(np.einsum("gepn,gn->gep", s, np.asarray(cm[t]))
                  + np.asarray(d)[..., None] * np.asarray(x[t]))
    return np.stack(ys), s


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunked_scan_is_the_recurrence(chunk):
    ops = scan_operands(32)
    want_y, want_s = recurrence(*ops)
    y, s = ssm.ssd_chunked(*ops, chunk)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-5)


def test_scan_carries_its_state_across_calls_and_ignores_dt_zero():
    """Two calls of 16 = one of 32 (the state at the boundary carries all),
    and positions with dt = 0 advance nothing (the padded end of a chunk)."""
    x, dt, a, bm, cm, d, s0 = scan_operands(32, seed=3)
    whole_y, whole_s = ssm.ssd_chunked(x, dt, a, bm, cm, d, s0, 8)
    y1, s1 = ssm.ssd_chunked(x[:16], dt[:16], a, bm[:16], cm[:16], d, s0, 8)
    y2, s2 = ssm.ssd_chunked(x[16:], dt[16:], a, bm[16:], cm[16:], d, s1, 8)
    np.testing.assert_allclose(np.concatenate([y1, y2]), whole_y,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s2, whole_s, rtol=1e-5, atol=1e-5)
    masked = dt.at[20:].set(0.0)
    _, s20 = ssm.ssd_chunked(x, masked, a, bm, cm, d, s0, 8)
    _, want = recurrence(x[:20], dt[:20], a, bm[:20], cm[:20], d, s0)
    np.testing.assert_allclose(s20, want, rtol=1e-5, atol=1e-5)


def test_state_update_is_one_step_of_the_recurrence_and_parks():
    x, dt, a, bm, cm, d, s0 = scan_operands(3, seed=4)
    state = jnp.stack([s0, 2 * s0, 3 * s0])
    live = jnp.array([True, False, True])
    y, new = ssm.ssm_state_update(x, dt, a, bm, cm, d, state, live)
    for b in (0, 2):
        wy, ws = recurrence(x[b:b + 1], dt[b:b + 1], a, bm[b:b + 1],
                            cm[b:b + 1], d, state[b])
        np.testing.assert_allclose(y[b], wy[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(new[b], ws, rtol=1e-5, atol=1e-5)
    assert (np.asarray(new[1]) == np.asarray(state[1])).all()


def whole_batch_update(x, dt, a, bm, cm, d, state):
    """The decode update over every slot, in plain ``jnp`` (the body the
    kernel replaced): what each live slot must read."""
    keep = jnp.exp(dt * a)[..., None, None]
    add = (dt[..., None] * x)[..., None] * bm[:, :, None, None, :]
    new = state * keep + add
    y = jnp.sum(new * cm[:, :, None, None, :], axis=-1) + x * d[..., None]
    return y, new


@pytest.mark.parametrize("live", [
    [0, 0, 0, 0, 0], [1, 1, 1, 1, 1], [0, 0, 1, 0, 0], [0, 0, 0, 0, 1],
    [0, 1, 0, 1, 1]], ids=["none", "all", "one", "last", "scattered"])
def test_state_update_moves_only_the_live_slots(live):
    """The kernel in the TPU interpreter (its buffers start as NaN): the
    live slots read the whole-batch formula, a parked slot's state comes
    back bit for bit and its ``y`` is finite — with no live slot too,
    where place 0 still visits a slot.  Toy widths, one head block."""
    x, dt, a, bm, cm, d, _ = (v.astype(jnp.float32)
                              for v in scan_operands(5, seed=7))
    g, e, p, n = 2, 4, 8, 16
    state = jax.random.normal(jax.random.PRNGKey(8), (5, g, e, p, n),
                              jnp.float32)
    want_y, want_s = whole_batch_update(x, dt, a, bm, cm, d, state)
    y, new = ssm.ssm_state_update(x, dt, a, bm, cm, d, state,
                                  jnp.array(live, bool),
                                  interpret=pltpu.InterpretParams())
    y, new, state = np.asarray(y), np.asarray(new), np.asarray(state)
    for b, on in enumerate(live):
        if on:
            np.testing.assert_allclose(new[b], want_s[b], rtol=1e-6,
                                       atol=1e-6)
            np.testing.assert_allclose(y[b], want_y[b], rtol=1e-5,
                                       atol=1e-5)
        else:
            assert (new[b] == state[b]).all()
            assert np.isfinite(y[b]).all()


def test_conv_tail_is_carried_across_a_chunk_boundary():
    k, c, t = 4, 12, 24
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (1, t, c))
    w, b = jax.random.normal(ks[1], (k, c)), jax.random.normal(ks[2], (c,))
    want = jax.nn.silu(ref.conv1d_causal(x[0], w, b))
    zero = jnp.zeros((1, k - 1, c))
    whole, _ = ssm.causal_conv1d(x, zero, w, b)
    np.testing.assert_allclose(whole[0], want, atol=1e-6)
    # 10 + 14, and the second piece's last 5 positions are padding: the tail
    # after it is cut behind its 9th input
    y1, xx = ssm.causal_conv1d(x[:, :10], zero, w, b)
    tail = ssm.conv_tail_after(xx, 10, k)
    y2, xx2 = ssm.causal_conv1d(x[:, 10:], tail, w, b)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1)[0], want,
                               atol=1e-6)
    np.testing.assert_array_equal(ssm.conv_tail_after(xx2, 9, k)[0],
                                  x[0, 16:19])


# (b) the serving programs through the cache, logits at every position
def programs_with_logits(stash):
    """Fresh jits of the two programs whose sampling also hands the logits
    to ``stash`` (the module-level jits answer from their trace cache)."""
    def tapped(logits):
        jax.debug.callback(lambda x: stash.append(np.asarray(x)), logits)
        return jnp.argmax(logits, -1).astype(jnp.int32), \
            jnp.all(jnp.isfinite(logits), -1)

    # new function objects: jit's trace cache is keyed by the function
    def prefill(params, cfg, tokens, offset, prompt_len, caches, slot,
                chunk_size):
        return fd._serving_prefill_chunk_impl(
            params, cfg, tokens, offset, prompt_len, caches, slot,
            chunk_size=chunk_size)

    def decode(params, cfg, cur, caches, lengths, chunk_size):
        return fd._serving_decode_steps_impl(
            params, cfg, cur, caches, lengths, n_steps=1,
            chunk_size=chunk_size)

    statics = ("cfg", "chunk_size")
    return tapped, jax.jit(prefill, static_argnames=statics), \
        jax.jit(decode, static_argnames=statics)


@pytest.mark.parametrize("plen", [5, 16, 21, 40])
def test_prefill_chunks_then_decode_give_the_reference_logits(
        config, model, monkeypatch, plen):
    """A prompt in chunks of 16 (lengths that are and are not a multiple of
    the chunk, one to three chunks) into slot 1 of 2, then 6 decode steps
    through the cache: the logits at the prompt's last position and at
    every decoded position are the reference's full-forward logits."""
    stash = []
    tapped, prefill, decode = programs_with_logits(stash)
    monkeypatch.setattr(fd, "_greedy_pick", tapped)
    fam = family_of(model)
    params, cfg = fam.decode_params(model, LMAX)
    caches = [fam.init_layer_cache(cfg, 2, LMAX, "float32")
              for _ in params["layers"]]
    # a dirty slot: the previous tenant's state and rows are still there
    caches = [tuple(jnp.full(leaf.shape, 0.7, leaf.dtype) for leaf in layer)
              for layer in caches]
    prompt, = prompts((plen,), seed=plen)
    padded = np.zeros((-(-plen // 16) * 16,), np.int32)
    padded[:plen] = prompt
    slot, plen_dev = jnp.int32(1), jnp.asarray([plen], jnp.int32)
    for off in range(0, plen, 16):
        first, ok, caches, _, _ = prefill(
            params, cfg, jnp.asarray(padded[None, off:off + 16]),
            jnp.int32(off), plen_dev, caches, slot, chunk_size=16)
    served, cur, n = [int(first[0])], first[0], plen
    logits = [stash[-1][0]]
    for _ in range(6):
        lengths = jnp.asarray([LMAX, n], jnp.int32)       # slot 0 parked
        toks, ok, caches = decode(params, cfg, jnp.stack([cur, cur]),
                                  caches, lengths, chunk_size=16)
        assert bool(ok[1])
        logits.append(stash[-1][1])
        cur, n = toks[1, 0], n + 1
        served.append(int(cur))
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    want = reference_logits(config, seq, plen - 1 + np.arange(7))
    np.testing.assert_allclose(np.stack(logits), want, atol=LOGIT_TOL,
                               rtol=0)
    # the parked slot's state and tail are bit for bit what they were
    for _, _, state, tail in caches:
        assert (np.asarray(state[0]) == np.float32(0.7)).all()
        assert (np.asarray(tail[0]) == np.float32(0.7)).all()


def gap_under_reference(config, r):
    """Widest gap by which a served token's reference logit lies below the
    reference's best (the benchmark's ``logit_gap_max``)."""
    out = np.asarray(r.output_ids, np.int32)
    seq = np.concatenate([r.prompt_ids, out[:-1]])
    want = reference_logits(config, seq,
                            len(r.prompt_ids) - 1 + np.arange(len(out)))
    return float((want.max(-1) - want[np.arange(len(out)), out]).max())


def test_engine_serves_the_references_tokens(config, model):
    """Continuous batching over 2 slots: 6 requests of mixed lengths, so
    every slot is reused, prompts are admitted while others decode, and
    chunks of several prompts interleave."""
    eng = engine(model)
    reqs = [eng.submit(Request(p, n)) for p, n in zip(
        prompts((21, 9, 30, 16, 32, 3), seed=2), (5, 7, 4, 6, 3, 8))]
    eng.run()
    assert [r.status for r in reqs] == ["done"] * 6
    assert max(gap_under_reference(config, r) for r in reqs) <= LOGIT_TOL


# (c) a reused slot: the next tenant gets a clean state
def state_of(eng, slot):
    return [(np.asarray(layer[2][slot]), np.asarray(layer[3][slot]))
            for layer in eng._kv.caches]


def test_a_reused_slot_serves_what_a_fresh_engine_serves(model):
    """One slot, two requests one after the other: the second request's
    tokens AND the slot's final state and tail are those of a fresh engine
    that only ever saw the second — the first tenant, the pipeline's
    one-step-late stale step after it retired, and the padded end of its
    last chunk left nothing behind."""
    first, second = prompts((27, 19), seed=6)
    used = engine(model, batch_size=1)
    a = used.submit(Request(first, 9))
    b = used.submit(Request(second, 7))
    used.run()
    fresh = engine(model, batch_size=1)
    c = fresh.submit(Request(second, 7))
    fresh.run()
    assert a.status == b.status == c.status == "done"
    assert list(b.output_ids) == list(c.output_ids)
    for (s1, t1), (s2, t2) in zip(state_of(used, 0), state_of(fresh, 0)):
        np.testing.assert_array_equal(s1, s2)
        np.testing.assert_array_equal(t1, t2)


# (c') the chunks a step spends on one prompt ride in ONE run
def wide(model):
    return W.family(engine, model)


@functools.lru_cache(maxsize=None)
def _served_wide(model, budget, length):
    return W.streams(W.serve(wide(model), budget, length)[1])


@pytest.mark.parametrize("length", W.LENGTHS)
@pytest.mark.parametrize("budget", W.BUDGETS)
def test_wide_runs_serve_the_chunk_a_run_engines_streams(model, budget,
                                                         length):
    """Whatever the budget, tokens and finite flags are those of the engine
    that runs a chunk a run (``prefill_budget=1``): the scan carries the
    state across the SSD chunks of a run as the cache carries it across
    runs, and a padded tail leaves it alone."""
    got = _served_wide(model, budget, length)
    assert [s for s, _ in got] == ["done", "done"]
    assert got == _served_wide(model, 1, length)


@pytest.mark.parametrize("length", [W.P + 1, 3 * W.P, 4 * W.P + 1])
def test_state_after_the_final_run_is_the_chunk_a_run_engines(model, length):
    """The slot's recurrent state and conv tail when its prompt's last
    chunk has gone out, one wide run or several narrow ones (the same
    products in another grouping: float32 rounding apart)."""
    def final_state(budget):
        eng = wide(model)(budget, batch_size=1)
        eng.submit(Request(W.prompt(length, 3), W.NEW))
        while eng._pf or not eng._kv.occupied():
            eng.step()
        return state_of(eng, 0)

    for (s1, t1), (s2, t2) in zip(final_state(4), final_state(1)):
        np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(t1, t2, rtol=1e-5, atol=1e-6)


def test_every_width_is_compiled_by_the_first_prefill_step(model):
    W.check_warm_set(wide(model), fd._mon)


def test_sync_every_steps_share_the_program(config, model):
    """``sync_every=3``: three tokens a dispatch through the inner scan,
    the state carried from step to step inside the program."""
    eng = engine(model, sync_every=3)
    reqs = [eng.submit(Request(p, 7)) for p in prompts((13, 22), seed=7)]
    eng.run()
    assert max(gap_under_reference(config, r) for r in reqs) <= LOGIT_TOL


def test_resume_after_a_preemption_remakes_the_state(config, model):
    """Preemption is the paged engine's (``_maybe_preempt``), which this
    model refuses at construction; what a resume does is re-admit the
    prompt PLUS the tokens already emitted through the ordinary chunked
    prefill (``_admission_ids``).  Driven directly: a request that already
    holds 5 emitted tokens is admitted into a used slot and continues the
    stream the reference continues."""
    eng = engine(model, batch_size=1)
    eng.submit(Request(prompts((25,), seed=8)[0], 6))
    eng.run()
    whole = engine(model, batch_size=1)
    r0 = whole.submit(Request(prompts((18,), seed=9)[0], 11))
    whole.run()
    resumed = Request(r0.prompt_ids, 11)
    eng.submit(resumed)
    resumed.output_ids = list(r0.output_ids[:5])    # as a preempted request
    resumed.preempts = 1
    eng.run()
    assert list(resumed.output_ids) == list(r0.output_ids)
    assert gap_under_reference(config, resumed) <= LOGIT_TOL


def test_poisoned_state_is_quarantined_and_does_not_outlive_its_tenant(model):
    """A NaN in one slot's recurrent state (the fault plan's poison lands
    there for a model that has one): that request retires ``poisoned``, its
    cohabitant's stream is untouched, and the slot's next tenant serves
    what a clean engine serves (the reset is a select, not a multiply)."""
    ps = prompts((20, 14, 17), seed=10)
    clean = engine(model)
    want = [clean.submit(Request(p, 8)) for p in ps]
    clean.run()
    eng = engine(model, faults=FaultPlan(poison={0: 2}))
    got = [eng.submit(Request(p, 8)) for p in ps]
    eng.run()
    assert [r.status for r in got] == ["poisoned", "done", "done"]
    assert list(got[1].output_ids) == list(want[1].output_ids)
    assert list(got[2].output_ids) == list(want[2].output_ids)


def test_state_counters(model):
    reg = MetricsRegistry()
    eng = engine(model, registry=reg)
    for p in prompts((21, 9, 30), seed=11):
        eng.submit(Request(p, 4))
    eng.run()
    lbl = dict(policy="continuous")
    cfg = statics_of(model.config)
    per_slot = (cfg.ssm_heads * cfg.ssm_head_dim * cfg.d_state * 4
                + (cfg.d_conv - 1) * cfg.conv_channels * 4)
    assert reg.get("serving_state_bytes").labels(**lbl).value \
        == 2 * per_slot * model.config.num_hidden_layers
    assert reg.get("serving_state_resets_total").labels(**lbl).value == 3
    read = reg.get("serving_state_slots_read_total").labels(**lbl)
    skipped = reg.get("serving_state_slots_skipped_total").labels(**lbl)
    assert read.value > 0 and (read.value + skipped.value) % 2 == 0
    # at a known mask: each of the dispatch's sync_every steps reads the
    # live slot and skips the parked one, then reads both
    for mask, live in (([True, False], 1), ([True, True], 2)):
        r0, s0 = read.value, skipped.value
        eng._decode_lengths(np.array(mask))
        assert read.value - r0 == live * eng._sync
        assert (read.value - r0) + (skipped.value - s0) == 2 * eng._sync
    # a model without recurrent state reports none
    reg2 = MetricsRegistry()
    llama = LlamaForCausalLM(LlamaConfig.tiny(dtype="float32"))
    llama.eval()
    eng2 = ServingEngine(llama, batch_size=2, max_len=LMAX, registry=reg2)
    eng2.submit(Request(prompts((9,))[0], 3))
    eng2.run()
    for name in ("serving_state_bytes", "serving_state_resets_total",
                 "serving_state_slots_read_total",
                 "serving_state_slots_skipped_total"):
        assert reg2.get(name).labels(**lbl).value == 0


# (e) what cannot be served raises at construction, naming what is missing
@pytest.mark.parametrize("option,missing", [
    (dict(mode="spec"), "roll the recurrent state back"),
    (dict(kv_block=16), "state snapshot per block"),
    (dict(kv_block=16, host_tier_bytes=1 << 20), "state snapshot per block"),
    (dict(kv_block=16, prefill_only=True), "state snapshot per block"),
    (dict(kv_dtype="int8"), "drift budget"),
    (dict(weight_dtype="int8"), "no int8 weight quantizer"),
    (dict(attn_impl="pallas"), "fused cache-read kernel"),
    (dict(prefill_impl="pallas"), "fused prefill kernel"),
    (dict(prefill_chunk=None), "chunked prefill is the only prefill"),
    (dict(prefill_chunk=12), "multiple of mamba_chunk_size"),
])
def test_unsupported_options_raise_at_construction(model, option, missing):
    with pytest.raises(ValueError, match=missing):
        engine(model, **option)


def test_mesh_raises_at_construction(model):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("mp",))
    with pytest.raises(ValueError, match="tensor-parallel rule set"):
        engine(model, mesh=mesh)


def test_adopt_prefilled_has_no_engine_to_run_on(model):
    """``adopt_prefilled`` splices an imported K/V block chain: it needs the
    paged engine, which this model refuses; on the dense engine that does
    construct it raises as it does for every model."""
    eng = engine(model)
    with pytest.raises((ValueError, RuntimeError, AttributeError)):
        eng.adopt_prefilled(Request(prompts((9,))[0], 3), 1, [])


def test_a_model_without_a_family_is_refused():
    class NoFamily:
        pass

    with pytest.raises(TypeError, match="serving_family"):
        ServingEngine(NoFamily(), batch_size=1, max_len=LMAX)


def test_llama_family_is_the_llama_programs():
    """The seam's first implementation hands the engine the very functions
    it used to import by name."""
    from paddle_tpu.models import llama_decode as ld

    llama = LlamaForCausalLM(LlamaConfig.tiny(dtype="float32"))
    fam = family_of(llama)
    assert fam.name == "llama" and fam.state_leaves == ()
    assert fam.decode_steps is ld.serving_decode_steps
    assert fam.prefill_chunk is ld.serving_prefill_chunk
    assert not hasattr(fam, "prefill_slot")
    assert fam.spec_step is ld.serving_spec_step
    assert fam.spec_draft_step is ld.serving_spec_draft_step
    params, cfg = fam.decode_params(llama, LMAX)
    assert fam.rows_leaves(cfg) == RowsLeaves(2, (2, 16), 4)
    k, v = fam.init_layer_cache(cfg, 3, LMAX, "float32")
    assert k.shape == v.shape == (3, LMAX, 2, 16)
    assert dataclasses.is_dataclass(fam)
