"""GLM-4.7-Flash (``glm4_moe_lite``: latent attention, routed experts plus a
shared one) against its plain float32 reference
(``benchmark/lib/glm4_moe_lite_ref.py``), on seeded weights at a tiny size
(3 layers — 1 dense + 2 expert —, 8 experts top-2 + shared, ``kv_lora_rank``
16): the model's forward, the expert FFN, the serving programs through the
latent cache, the engine's recorded routes.

Tolerances.  Everything here is float32 at matmul precision "highest"
(``conftest.py``), so the program and the reference differ by summation
order alone: logits of O(1) agree to a few 1e-6 (measured 2.7e-6 for the
whole-sequence forward); ``LOGIT_TOL`` = 2e-4 leaves room for the absorbed
form's different association and the chunked read, and is far under what a
wrong route produces (a swapped expert reads 0.1-1).  In float32 the
program's routes ARE the reference's own.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _wide_runs as wide_runs
import paddle_tpu as paddle
from benchmark.lib import glm4_moe_lite_ref as ref
from benchmark.lib import glm4_moe_lite_weights as W
from benchmark.models import glm4_moe_lite as arch
from paddle_tpu.models import glm4_moe_lite_decode as gd
from paddle_tpu.models.glm4_moe_lite import (
    Glm4MoeLiteConfig, Glm4MoeLiteForCausalLM, block_forward, statics_of,
)
from paddle_tpu.models.serving_family import RowsLeaves, family_of
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.ops import moe
from paddle_tpu.ops.decode_attention import decode_attention
from paddle_tpu.serving import Request, ServingEngine

LOGIT_TOL = 2e-4
SEED = 2147483777
LMAX = 64


def tiny_config():
    c = Glm4MoeLiteConfig.tiny()
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


def reference_logits(config, seq, rows, **kw):
    tokens = np.zeros((1, LMAX), np.int32)
    tokens[0, :len(seq)] = seq
    out, = arch.serve_logits(config, SEED, tokens,
                             np.asarray(rows, np.int32)[None], **kw)
    return out[0]


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).astype(np.int32) for n in lengths]


def engine(model, **kw):
    kw = dict(dict(batch_size=2, max_len=LMAX, prefill_chunk=16,
                   decode_chunk=16), **kw)
    return ServingEngine(model, **kw)


# (a) the Layer model's whole-sequence forward, and the two attention forms
def test_forward_is_the_reference(config, model):
    toks = np.stack(prompts((40, 40), seed=1))
    want, = arch.serve_logits(config, SEED, toks,
                              np.tile(np.arange(40, dtype=np.int32), (2, 1)))
    got = np.asarray(model(paddle.to_tensor(toks)).data)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    assert 0.3 < want.std() < 3.0               # logits of O(1)


def test_parameters_are_born_in_their_dtype():
    m = Glm4MoeLiteForCausalLM(Glm4MoeLiteConfig.tiny(dtype="bfloat16"))
    kinds = {str(p.data.dtype) for _, p in m.named_parameters()}
    assert kinds == {"bfloat16", "float32"}     # the bias alone is float32
    assert [n for n, p in m.named_parameters()
            if str(p.data.dtype) == "float32"] == [
        f"model.layers.{i}.mlp.gate.e_score_correction_bias" for i in (1, 2)]


def test_absorbed_attention_is_the_expanded_one(config, model):
    """One layer over a whole sequence: the serving form (``W_uk`` folded
    into the query, the cache's ``[c | k_r]`` rows, ``W_uv`` behind the
    sum) against ``block_forward``'s expanded form."""
    fam = family_of(model)
    params, cfg = fam.decode_params(model, LMAX)
    lp = params["layers"][0]
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((1, 24, 64)), jnp.float32)
    cos_t, sin_t = params["_rope"]
    want = block_forward(lp, cfg, h, cos_t, sin_t)
    cache = fam.init_layer_cache(cfg, 1, LMAX, "float32")
    got, _, routes = gd._layer_prefill(
        lp, cfg, h, cache, jnp.int32(0), jnp.int32(0), jnp.int32(24), cos_t,
        sin_t, None)
    assert routes is None                       # the dense layer
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=0)


# (b) the expert FFN under uneven routing
def looped_ffn(x, experts, gates, live, w_gate, w_up, w_down):
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        if not live[t]:
            continue
        for e, g in zip(experts[t], gates[t]):
            hid = x[t] @ w_gate[e]
            hid = hid / (1 + np.exp(-hid)) * (x[t] @ w_up[e])
            out[t] += g * (hid @ w_down[e])
    return out


@pytest.mark.parametrize("case", ["uneven", "one_takes_all", "all_parked"])
def test_expert_ffn_is_the_looped_reference(case):
    rng = np.random.default_rng(4)
    t, h, f, e, k = 12, 16, 24, 8, 2
    x = rng.standard_normal((t, h)).astype(np.float32)
    w = [rng.standard_normal(s).astype(np.float32) * 0.3
         for s in ((e, h, f), (e, h, f), (e, f, h))]
    live = np.ones(t, bool)
    if case == "uneven":
        # expert 3 gets no token, expert 0 most; rows 2 and 7 are parked
        experts = rng.choice([0, 0, 0, 1, 2, 4, 5, 6, 7], (t, k))
        experts[:, 1] = (experts[:, 0] + 1 + (experts[:, 0] == 2)) % 8
        experts[experts == 3] = 4
        live[[2, 7]] = False
    elif case == "one_takes_all":
        experts = np.tile([5, 1], (t, 1))       # experts 5 and 1, every row
    else:
        experts, live = rng.integers(0, e, (t, k)), np.zeros(t, bool)
    gates = rng.uniform(0.2, 1.0, (t, k)).astype(np.float32)
    got = moe.expert_ffn(jnp.asarray(x), jnp.asarray(experts, jnp.int32),
                         jnp.asarray(gates), jnp.asarray(live),
                         *(jnp.asarray(a) for a in w))
    want = looped_ffn(x, experts, gates, live, *w)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-5)
    # rows that are not live reach no expert: exactly nothing comes back
    assert (np.asarray(got)[~live] == 0).all()
    assert (moe.live_routes(jnp.asarray(experts), jnp.asarray(live))
            [~live] == -1).all()


def test_router_is_the_references(config, model):
    lp = family_of(model).decode_params(model, LMAX)[0]["layers"][1]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((50, 64)),
                    jnp.float32)
    experts, gates = moe.route(x, lp["router"], lp["router_bias"], 2, 1.8)
    d = W.dims_of(arch.sizes(config))
    p = {k: lp[k] for k in ("router", "router_bias")}
    want_e, want_g, _ = ref.choose(p, x, d, jnp.full((50, 2), -1), 0.0)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(want_e, -1))
    np.testing.assert_allclose(np.sort(gates, -1), np.sort(want_g, -1),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.8, atol=1e-5)
    # the bias chooses: without it some choices differ
    plain, _ = moe.route(x, lp["router"], lp["router_bias"] * 0 + 0.0, 2,
                         1.8)
    far, _ = moe.route(x, lp["router"], lp["router_bias"] * 40, 2, 1.8)
    assert (np.sort(far, -1) != np.sort(plain, -1)).any()


# (c) the latent leaf is a case of the shared read
def test_latent_read_is_the_kv_read_of_the_same_rows():
    """``v_width``: ONE leaf whose first columns are the values, against
    the (k, v) read of a K leaf holding the rows and a V leaf holding
    their first columns — the same numbers through the same loop."""
    # 8 slots: the per-slot read (blocks of 4) with chunk 16, the full read
    # without
    rng = np.random.default_rng(6)
    b, lmax, r, vw, heads = 8, 48, 24, 16, 4
    rows = jnp.asarray(rng.standard_normal((b, lmax, r)), jnp.float32)
    lengths = jnp.asarray([5, 48, 0, 17, 30, 9, 48, 40], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, 1, heads, r)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((b, 1, 1, r)), jnp.float32)
    for chunk in (None, 16):
        got, rows2, none, _ = decode_attention(
            q, new, None, rows, None, lengths, scale=0.25, chunk_size=chunk,
            v_width=vw)
        # a V leaf as wide as the K leaf (the (k, v) read wants one
        # geometry): the rows with the columns behind ``vw`` zeroed
        mask = (jnp.arange(r) < vw).astype(jnp.float32)
        want, k2, _, _ = decode_attention(
            q, new, new * mask, rows[:, :, None], (rows * mask)[:, :, None],
            lengths, scale=0.25, chunk_size=chunk)
        assert none is None and rows2.shape == rows.shape
        live = np.asarray(lengths) < lmax
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live][..., :vw],
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(rows2),
                                      np.asarray(k2[:, :, 0]))
    with pytest.raises(ValueError, match="one latent rows leaf"):
        decode_attention(q, new, new, rows, rows, lengths, v_width=vw)


def _uneven(rng, batch, live, lmax):
    """``live`` slots of uneven lengths (one empty, one a row short of the
    span) scattered over a batch whose other slots are parked."""
    lengths = np.full((batch,), lmax, np.int32)
    lens = rng.integers(1, lmax - 1, live)
    lens[:2] = (0, lmax - 1)
    lengths[rng.permutation(batch)[:live]] = lens
    return lengths


# dtype, batch, span, chunk, live slots, rows a trip's gather groups
GROUPED_READS = {
    "bf16-clamped-tail": ("bfloat16", 8, 80, 32, 6, 16),
    "f32-clamped-tail": ("float32", 8, 40, 16, 6, 8),
    "bf16-parked": ("bfloat16", 16, 64, 16, 3, 16),
    "bf16-64x7": ("bfloat16", 64, 96, 32, 7, 16),
    "bf16-64x33": ("bfloat16", 64, 96, 32, 33, 16),
    "f32-64x33": ("float32", 64, 48, 16, 33, 8),
    # the fall-back geometry: a span the group does not divide
    "bf16-flat-span": ("bfloat16", 8, 72, 16, 6, 1),
    "f32-flat-chunk": ("float32", 8, 48, 12, 6, 1),
}


@pytest.mark.parametrize("case", sorted(GROUPED_READS))
def test_grouped_latent_read_is_bitwise_the_flat_read(case, monkeypatch,
                                                      caplog):
    """The per-slot read of ONE latent rows leaf gathers a block's chunk
    from the ``[B * Lmax / S, S, R]`` view (``S`` rows a tile of the dtype:
    16 bfloat16, 8 float32) wherever ``S`` divides span and chunk: the same
    rows in the same trips as the flat ``[B * Lmax, 1, R]`` view's, so every
    output — parked slots' too — is BITWISE the flat read's, the full read's
    to rounding on live slots, and the appended leaf the same.  A geometry
    the group does not divide keeps the flat view and says so once."""
    from paddle_tpu.ops import decode_attention as da

    dtype, b, lmax, chunk, live, group = GROUPED_READS[case]
    assert da._slot_block(b) is not None
    assert lmax % chunk or "tail" not in case
    rng = np.random.default_rng(36)
    r, vw, heads = 24, 16, 4
    rows = jnp.asarray(rng.standard_normal((b, lmax, r)), dtype)
    lengths = jnp.asarray(_uneven(rng, b, live, lmax))
    q = jnp.asarray(rng.standard_normal((b, 1, heads, r)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((b, 1, 1, r)), dtype)

    def read(chunk_size):
        # a fresh jit: ``decode_attention`` answers from its trace cache
        return jax.jit(lambda *a: da.decode_attention.__wrapped__(
            *a, scale=0.25, chunk_size=chunk_size, v_width=vw))(
                q, new, None, rows, None, lengths)

    da._flat_views.clear()
    seen = []
    tile_rows = da._tile_rows
    monkeypatch.setattr(da, "_tile_rows", lambda *a: seen.append(
        tile_rows(*a)) or seen[-1])
    with caplog.at_level("WARNING", logger=da.__name__):
        got, leaf, _, _ = read(chunk)
        read(chunk)
    assert seen == [group, group]
    assert len([m for m in caplog.messages if "flat view" in m]) == (
        group == 1)
    monkeypatch.setattr(da, "_tile_rows", lambda *a: 1)
    flat, flat_leaf, _, _ = read(chunk)
    full, full_leaf, _, _ = read(None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(flat))
    for other in (flat_leaf, full_leaf):
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(other, np.float32))
    on = np.asarray(lengths) < lmax
    assert on.sum() == live and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[on], np.asarray(full)[on],
                               atol=1e-5)


@pytest.mark.parametrize("family", ["llama", "falcon_h1"])
def test_kv_families_never_take_the_one_leaf_read(family, monkeypatch):
    """The (k, v) families' decode programs (the Mistral and the Falcon-H1
    cells') read through the per-slot branch as before: the rows' group is
    never asked for, and a layer's two reads are gathers of ``[C, Hkv, D]``
    windows of the ``[B * Lmax, Hkv, D]`` view.  (The lowered texts of both
    programs at the tiny size are the parent's, line for line: PR 36's
    check against a ``git archive`` copy.)"""
    from paddle_tpu.models import falcon_h1_decode, llama_decode
    from paddle_tpu.models.falcon_h1 import (FalconH1Config,
                                             FalconH1ForCausalLM)
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.ops import decode_attention as da

    def never(*a):
        raise AssertionError("a (k, v) cache asked for the rows' group")

    monkeypatch.setattr(da, "_tile_rows", never)
    paddle.seed(0)
    if family == "llama":
        m, programs = LlamaForCausalLM(LlamaConfig.tiny(dtype="float32")), \
            llama_decode
    else:
        m, programs = FalconH1ForCausalLM(FalconH1Config.tiny()), \
            falcon_h1_decode
    m.eval()
    # 8 slots x 80 rows: the per-slot read in blocks of 4, on a geometry no
    # other test traces (``decode_attention`` is a jit of its own)
    eng = ServingEngine(m, batch_size=8, max_len=80, prefill_chunk=16,
                        decode_chunk=16)
    assert da._slot_block(8) == 4
    rows = jnp.zeros((8,), jnp.int32)
    text = programs.serving_decode_steps.__wrapped__.lower(
        eng._params, eng._cfg, rows, eng._kv.caches, rows,
        n_steps=eng._sync, chunk_size=eng._chunk, block_tables=None,
        program_key=eng._pk).as_text()
    k = eng._kv.caches[0][0]
    hkv, d = k.shape[2], k.shape[3]
    windows = re.findall(
        r"stablehlo\.gather.*slice_sizes = array<i64: 16, %d, %d>.*"
        r"tensor<%dx%dx%dx" % (hkv, d, 8 * 80, hkv, d), text)
    # (``decode_attention`` is ONE function of the module, called a layer:
    # K's read and V's)
    assert len(windows) == 2, len(windows)


# (d) the serving programs through the cache, logits at every position
def programs_with_logits(stash):
    """Fresh jits of the two programs whose sampling also hands the logits
    to ``stash`` (the module-level jits answer from their trace cache)."""
    def tapped(logits):
        jax.debug.callback(lambda x: stash.append(np.asarray(x)), logits)
        return jnp.argmax(logits, -1).astype(jnp.int32), \
            jnp.all(jnp.isfinite(logits), -1)

    def prefill(params, cfg, tokens, offset, prompt_len, caches, slot,
                chunk_size):
        return gd._serving_prefill_chunk_impl(
            params, cfg, tokens, offset, prompt_len, caches, slot,
            chunk_size=chunk_size)

    def decode(params, cfg, cur, caches, lengths, chunk_size):
        return gd._serving_decode_steps_impl(
            params, cfg, cur, caches, lengths, n_steps=1,
            chunk_size=chunk_size)

    statics = ("cfg", "chunk_size")
    return tapped, jax.jit(prefill, static_argnames=statics), \
        jax.jit(decode, static_argnames=statics)


@pytest.mark.parametrize("plen", [5, 16, 21, 40])
def test_prefill_chunks_then_decode_give_the_reference_logits(
        config, model, monkeypatch, plen):
    """A prompt in chunks of 16 (one to three chunks, lengths that are and
    are not a multiple of the chunk) into slot 1 of 2, then 6 decode steps
    through the latent cache: the logits at the prompt's last position and
    at every decoded position are the reference's full-forward logits, and
    the recorded routes are the reference's own."""
    stash = []
    tapped, prefill, decode = programs_with_logits(stash)
    monkeypatch.setattr(gd, "_greedy_pick", tapped)
    fam = family_of(model)
    params, cfg = fam.decode_params(model, LMAX)
    # a dirty slot beside it: the previous tenant's rows are still there
    caches = [tuple(jnp.full(leaf.shape, 0.7, leaf.dtype) for leaf in
                    fam.init_layer_cache(cfg, 2, LMAX, "float32"))
              for _ in params["layers"]]
    prompt, = prompts((plen,), seed=plen)
    padded = np.zeros((-(-plen // 16) * 16,), np.int32)
    padded[:plen] = prompt
    slot, plen_dev = jnp.int32(1), jnp.asarray([plen], jnp.int32)
    routes = []
    for off in range(0, plen, 16):
        first, ok, caches, _, _, r = prefill(
            params, cfg, jnp.asarray(padded[None, off:off + 16]),
            jnp.int32(off), plen_dev, caches, slot, chunk_size=16)
        n = min(16, plen - off)
        assert (np.asarray(r[n:]) == -1).all()  # the padded end routes nowhere
        routes.append(np.asarray(r[:n]))
    served, cur, n = [int(first[0])], first[0], plen
    logits = [stash[-1][0]]
    for _ in range(6):
        lengths = jnp.asarray([LMAX, n], jnp.int32)       # slot 0 parked
        toks, ok, caches, r = decode(params, cfg, jnp.stack([cur, cur]),
                                     caches, lengths, chunk_size=16)
        assert bool(ok[1])
        assert (np.asarray(r[0]) == -1).all()   # the parked slot
        routes.append(np.asarray(r[1]))
        logits.append(stash[-1][1])
        cur, n = toks[1, 0], n + 1
        served.append(int(cur))
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    want = reference_logits(config, seq, plen - 1 + np.arange(7))
    np.testing.assert_allclose(np.stack(logits), want, atol=LOGIT_TOL,
                               rtol=0)
    # the parked slot's rows are bit for bit what they were
    for (rows,) in caches:
        assert (np.asarray(rows[0]) == np.float32(0.7)).all()
    # recorded routes = the reference's own in float32: none differs
    rec = np.full((1, LMAX, 2, 2), -1, np.int32)
    got = np.concatenate(routes)[:len(seq)]
    rec[0, :len(seq)] = got
    st = {}
    reference_logits(config, seq, [0], routes=rec, stats=st)
    assert st["recorded"] == 2 * len(seq)
    assert st["differ"] == st["refused"] == st["followed"] == 0
    assert got.min() >= 0 and got.max() < 8


def gap_under_reference(config, r):
    """Widest gap by which a served token's reference logit lies below the
    reference's best (the benchmark's ``logit_gap_max``)."""
    out = np.asarray(r.output_ids, np.int32)
    seq = np.concatenate([r.prompt_ids, out[:-1]])
    want = reference_logits(config, seq,
                            len(r.prompt_ids) - 1 + np.arange(len(out)))
    return float((want.max(-1) - want[np.arange(len(out)), out]).max())


def recorded(r):
    return np.concatenate(r.routes, axis=0)


@pytest.mark.parametrize("sync_every", [1, 3])
def test_engine_serves_the_references_tokens_and_records_routes(
        config, model, sync_every):
    """Continuous batching over 2 slots, pipeline on: 6 requests of mixed
    lengths, so every slot is reused, prompts are admitted while others
    decode, and chunks of several prompts interleave.  Every request
    carries one row of routes a position the programs ran (the prompt,
    then each emitted token's input) and the reference, following them,
    refuses none and departs from its own choice nowhere."""
    eng = engine(model, sync_every=sync_every)
    reqs = [eng.submit(Request(p, n)) for p, n in zip(
        prompts((21, 9, 30, 16, 32, 3), seed=2), (5, 7, 4, 6, 3, 8))]
    eng.run()
    assert [r.status for r in reqs] == ["done"] * 6
    assert max(gap_under_reference(config, r) for r in reqs) <= LOGIT_TOL
    for r in reqs:
        p, n = len(r.prompt_ids), len(r.output_ids)
        rt = recorded(r)
        assert rt.shape == (p + n - 1, 2, 2) and rt.dtype == np.int8
        assert rt.min() >= 0
        rec = np.full((1, LMAX, 2, 2), -1, np.int32)
        rec[0, :len(rt)] = rt
        seq = np.concatenate([r.prompt_ids, r.output_ids[:-1]]).astype(
            np.int32)
        st = {}
        reference_logits(config, seq, [0], routes=rec, stats=st)
        assert (st["recorded"], st["differ"], st["refused"]) == (
            2 * len(rt), 0, 0)


def test_reference_refuses_a_planted_illegitimate_route(config):
    prompt, = prompts((20,), seed=9)
    rec = np.full((1, LMAX, 2, 2), -1, np.int32)
    rec[0, :20] = [0, 1]                        # one pair for every position
    st = {}
    got = reference_logits(config, prompt, np.arange(20), routes=rec,
                           stats=st)
    own = reference_logits(config, prompt, np.arange(20))
    assert st["recorded"] == 40 and st["refused"] > 20
    assert st["followed"] == st["differ"] - st["refused"]
    assert st["short"].size == st["differ"] and st["short"].min() > 0
    if not st["followed"]:                      # every choice kept its own
        np.testing.assert_array_equal(got, own)


# (e) a reused slot
def test_a_reused_slot_serves_what_a_fresh_engine_serves(model):
    """One slot, two requests one after the other: the second request's
    tokens and routes are those of a fresh engine that only ever saw the
    second, bit for bit — the first tenant's rows, the pipeline's
    one-step-late stale step and the padded end of its last chunk left
    nothing a length does not hide."""
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
    np.testing.assert_array_equal(recorded(b), recorded(c))
    n = 19 + 7 - 1
    for (r1,), (r2,) in zip(used._kv.caches, fresh._kv.caches):
        np.testing.assert_array_equal(np.asarray(r1[0, :n]),
                                      np.asarray(r2[0, :n]))


def park(eng):
    """``_preempt_slot`` less the paged pool's prefix registration (this
    family refuses the pool): the one resident request goes back to the
    queue with the tokens it has, and the record in flight is drained as
    the next step would drain it."""
    r = eng._kv.reqs[0]
    eng._kv.release(0)
    eng._forget_slot(0)
    r.preempts += 1
    eng._queue.appendleft(r)
    prev, eng._inflight = eng._inflight, None
    eng._drain(prev)


@pytest.mark.parametrize("when", ["mid_prefill", "mid_decode"])
def test_a_readmitted_request_carries_one_row_a_position(model, when):
    """A request that is parked and admitted again is prefilled from its
    first row again: its routes are recorded anew (not appended behind the
    first admission's), the chunks of the interrupted prefill that no
    dispatch had taken are dropped, and tokens and routes are a fresh
    engine's."""
    prompt, = prompts((40,), seed=13)
    fresh = engine(model, batch_size=1)
    c = fresh.submit(Request(prompt, 8))
    fresh.run()
    eng = engine(model, batch_size=1)
    r = eng.submit(Request(prompt, 8))
    if when == "mid_prefill":
        eng.step()          # two chunks of three in ONE run, no decode yet
        assert eng._pf and [n for _, n, _, _ in eng._chunk_routes] == [32]
    else:
        while len(r.output_ids) < 3:
            eng.step()
    park(eng)
    assert eng._chunk_routes == []
    eng.run()
    assert r.status == "done"
    assert list(r.output_ids) == list(c.output_ids)
    np.testing.assert_array_equal(recorded(r), recorded(c))
    assert len(recorded(r)) == 40 + 8 - 1


# (e') the chunks a step spends on one prompt ride in ONE run
def wide(model):
    return wide_runs.family(engine, model)


@functools.lru_cache(maxsize=None)
def _served_wide(model, budget, length):
    reqs = wide_runs.serve(wide(model), budget, length)[1]
    return wide_runs.streams(reqs), [recorded(r) for r in reqs]


@pytest.mark.parametrize("length", wide_runs.LENGTHS)
@pytest.mark.parametrize("budget", wide_runs.BUDGETS)
def test_wide_runs_serve_the_chunk_a_run_engines_streams_and_routes(
        model, budget, length):
    """Whatever the budget, tokens, finite flags and the recorded routes —
    row for row: one record a RUN, cut to its real rows — are those of the
    engine that runs a chunk a run (``prefill_budget=1``)."""
    got, routes = _served_wide(model, budget, length)
    want, want_routes = _served_wide(model, 1, length)
    assert [s for s, _ in got] == ["done", "done"]
    assert got == want
    for mine, theirs in zip(routes, want_routes):
        np.testing.assert_array_equal(mine, theirs)


def test_every_width_is_compiled_by_the_first_prefill_step(model):
    wide_runs.check_warm_set(wide(model), gd._mon)


# (f) counters
def test_expert_counters(model):
    reg = MetricsRegistry()
    eng = engine(model, registry=reg)
    reqs = [eng.submit(Request(p, 4)) for p in prompts((21, 9, 30), seed=11)]
    eng.run()
    pairs = reg.get("serving_moe_expert_tokens_total")
    snap = reg.snapshot()
    by = lambda name, label: {s["labels"][label]: s["value"]
                              for s in snap[name]["series"]}
    total = sum(by("serving_moe_expert_tokens_total", "expert").values())
    # every position the programs ran for a live row, 2 expert layers x 2;
    # the stale step after a request retires may add a few decode rows
    rows = sum(len(recorded(r)) for r in reqs)
    assert 4 * rows <= total <= 4 * (rows + 3 * len(reqs))
    runs = by("serving_moe_dispatches_total", "program")
    # chunks of 16, two a step, two slots: 21 rows (2 chunks) ride ONE
    # run, 9 take one, and 30 (admitted once a slot is free) one again
    assert runs["prefill"] == 1 + 1 + 1
    assert runs["decode"] >= 3
    touched = by("serving_moe_experts_touched_total", "program")
    assert 2 * runs["prefill"] <= touched["prefill"] <= 16 * runs["prefill"]
    assert 2 * 2 <= touched["decode"] <= 2 * 4 * runs["decode"]
    assert pairs is not None
    # a parked slot reaches no counter: one live slot of two touches at most
    # 2 experts a layer and step
    reg2 = MetricsRegistry()
    eng2 = engine(model, registry=reg2)
    eng2.submit(Request(prompts((9,))[0], 6))
    eng2.run()
    s2 = reg2.snapshot()
    t2 = {s["labels"]["program"]: s["value"] for s in
          s2["serving_moe_experts_touched_total"]["series"]}
    n2 = {s["labels"]["program"]: s["value"] for s in
          s2["serving_moe_dispatches_total"]["series"]}
    assert t2["decode"] <= 2 * 2 * n2["decode"]


# (g) what cannot be served raises at construction, naming what is missing
@pytest.mark.parametrize("option,missing", [
    (dict(mode="spec"), "MTP drafter"),
    (dict(kv_block=16), "paged pool whose block holds one latent row"),
    (dict(kv_dtype="int8"), "int8 latent row"),
    (dict(weight_dtype="int8"), "no int8 weight quantizer"),
    (dict(attn_impl="pallas"), "fused cache-read kernel"),
    (dict(prefill_impl="pallas"), "fused prefill kernel"),
    (dict(tp_overlap=2), "no mesh rule set"),
])
def test_unsupported_options_raise_at_construction(model, option, missing):
    with pytest.raises(ValueError, match=missing):
        engine(model, **option)


def test_mesh_raises_at_construction(model):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("mp",))
    with pytest.raises(ValueError, match="tensor-parallel rule set"):
        engine(model, mesh=mesh)


def test_family_states_its_rows_leaves(model):
    fam = family_of(model)
    params, cfg = fam.decode_params(model, LMAX)
    assert fam.name == "glm4_moe_lite" and fam.state_leaves == ()
    assert fam.spec_step is None and fam.tp_rules is None
    assert cfg.row == 24 and cfg.row_stored == 128
    assert fam.rows_leaves(cfg) == RowsLeaves(1, (1, 128), 4)
    (rows,) = fam.init_layer_cache(cfg, 3, LMAX, "float32")
    assert rows.shape == (3, LMAX, 128)
    assert fam.routed_experts(params) == 8
    assert statics_of(Glm4MoeLiteConfig()).row_stored == 640
    # a family without experts hands back nothing more than before
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    llama = LlamaForCausalLM(LlamaConfig.tiny(dtype="float32"))
    llama.eval()
    assert family_of(llama).routed_experts is None
    eng = ServingEngine(llama, batch_size=2, max_len=LMAX)
    r = eng.submit(Request(prompts((9,))[0], 3))
    eng.run()
    assert r.routes is None
