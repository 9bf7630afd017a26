"""Xing4.0-29B-A4B (``models/xing4.py``: the GLM family's attention and
expert FFN under 4-stream hyper-connections, YaRN positions) against its
plain float32 reference (``benchmark/lib/xing4_ref.py``), on seeded weights
at a tiny size (2 dense + 2 expert layers, 8 experts top-2 + shared,
``hc_mult`` 4 with 20 Sinkhorn steps, a YaRN ramp that is active inside the
128-row table): the whole-sequence forward, the serving programs through
the latent cache, the engine's recorded routes and counters, and the shared
programs' other tenant (GLM's tiny streams, bit for bit).

Tolerances.  Everything here is float32 at matmul precision "highest"
(``conftest.py``), so the program and the reference differ by summation
order alone (the absorbed attention's other association, the chunked read,
the Sinkhorn iteration carried as ``diag(r) K diag(c)``): logits of O(1)
agree to a few 1e-6 (measured 4.2e-6 for the whole-sequence forward).
``LOGIT_TOL`` = 2e-4 (GLM's) leaves room and is far under what a wrong
residual path produces: the mixing left out reads 0.5, ``H_post`` without
its factor 1.1, one Sinkhorn step 0.05, the YaRN scale left out 0.4
(measured here, ``test_a_departure_from_the_equations_moves_the_logits``).
In float32 the program's routes ARE the reference's own.
"""
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _wide_runs as wide_runs
import paddle_tpu as paddle
from test_glm4_moe_lite_serving import (
    engine, programs_with_logits, prompts, recorded,
)
from benchmark.models import glm4_moe_lite as glm_arch
from benchmark.models import xing4 as arch
from paddle_tpu.models import glm4_moe_lite_decode as gd
from paddle_tpu.models.glm4_moe_lite import (
    Glm4MoeLiteConfig, Glm4MoeLiteForCausalLM, rope_tables, statics_of,
)
from paddle_tpu.models.serving_family import RowsLeaves, family_of
from paddle_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.ops import hyper_connection
from paddle_tpu.serving import Request

LOGIT_TOL = 2e-4
SEED = 2147483777
LMAX = 64


def config_of(c):
    config = {k: getattr(c, k) for k in c.__dataclass_fields__}
    config["torch_dtype"] = "float32"
    return config


@pytest.fixture(scope="module")
def config():
    return config_of(Xing4Config.tiny())


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


# (a) the configuration and what it builds
def test_published_defaults_and_statics():
    c = Xing4Config()
    assert (c.hidden_size, c.num_hidden_layers, c.first_k_dense_replace,
            c.num_attention_heads, c.vocab_size) == (3584, 40, 2, 32, 131072)
    cfg = statics_of(c)
    assert (cfg.hc, cfg.hc_iters, cfg.hc_eps, cfg.hc_clamp) == (
        4, 20, 1e-6, (-30.0, 30.0))
    assert (cfg.row, cfg.row_stored) == (576, 640)
    # (128 + 64)^-1/2 x (0.1 ln 64 + 1)^2 = 0.07217 x 2.00474
    assert cfg.scale == pytest.approx(0.072169 * 2.004740, rel=1e-5)
    # GLM's statics carry no hyper-connection and the plain scale
    glm = statics_of(Glm4MoeLiteConfig())
    assert (glm.hc, glm.hc_iters, glm.scale_mult) == (1, 0, 1.0)
    assert glm.scale == 256 ** -0.5


def test_yarn_table_blends_the_frequencies():
    """Published keys: dims below 10 keep their frequency, dims above 23
    are divided by 64, the ramp between; cos / sin carry m(mscale) /
    m(mscale_all_dim) = 1.  Against the formula in NumPy."""
    c = Xing4Config()
    cos, sin = rope_tables(c, 512, "float32")
    j = np.arange(32)
    inv = 1e4 ** (-2.0 * j / 64)
    ramp = np.clip((j - 10) / 13, 0, 1)
    want = inv * (1 - ramp) + inv / 64 * ramp
    ang = np.outer(np.arange(512), want)
    np.testing.assert_allclose(np.asarray(cos)[:, :32], np.cos(ang),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(sin)[:, 32:], np.sin(ang),
                               atol=2e-4)
    assert (ramp[:11] == 0).all() and (ramp[23:] == 1).all()
    # the tiny preset's ramp is active inside its table: a kept, a blended
    # and two interpolated frequencies
    tiny = Xing4Config.tiny()
    t_cos, _ = rope_tables(tiny, 128, "float32")
    plain, _ = rope_tables(Glm4MoeLiteConfig.tiny(rope_theta=100.0), 128,
                           "float32")
    differs = np.abs(np.asarray(t_cos) - np.asarray(plain)).max(0)[:4]
    assert differs[0] == 0 and (differs[1:] > 0.1).all()


@pytest.mark.parametrize("bad,match", [
    (dict(rope_scaling={"type": "linear", "factor": 2}), "rope_scaling type"),
    (dict(hc_mult=0), "at least one residual stream"),
])
def test_config_refuses_what_has_no_path(bad, match):
    with pytest.raises(ValueError, match=match):
        Xing4Config.tiny(**bad)


def test_one_stream_builds_no_hyper_connection_parameter():
    plain = Xing4ForCausalLM(Xing4Config.tiny(hc_mult=1))
    assert not [n for n, _ in plain.named_parameters() if "hc" in n]
    assert not [k for layer in plain.model.layers for k in layer.weights()
                if k.startswith("hc")]
    four = Xing4ForCausalLM(Xing4Config.tiny(dtype="bfloat16"))
    hc = {n: p for n, p in four.named_parameters() if "_hc." in n}
    assert len(hc) == 4 * 2 * 3                 # layers x sub-layers x leaves
    assert {str(p.data.dtype) for p in hc.values()} == {"float32"}
    assert tuple(hc["model.layers.0.attn_hc.phi"].shape) == (4 * 64, 24)
    # a fresh model's bias: 2 on the diagonal of the res part
    b = np.asarray(hc["model.layers.3.mlp_hc.b"].data)
    np.testing.assert_array_equal(b[8:].reshape(4, 4), 2 * np.eye(4))


# (b) the whole-sequence forward
def test_forward_is_the_reference(config, model):
    toks = np.stack(prompts((40, 40), seed=1))
    want, = arch.serve_logits(config, SEED, toks,
                              np.tile(np.arange(40, dtype=np.int32), (2, 1)))
    got = np.asarray(model(paddle.to_tensor(toks)).data)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    assert 0.3 < want.std() < 3.0               # logits of O(1)


# (c) the serving programs through the cache, logits at every position
def served_logits(model, monkeypatch, plen, cfg_edit=None, n_decode=6):
    """A prompt in chunks of 16 into slot 1 of 2 (slot 0 dirty and
    parked), then ``n_decode`` decode steps: (sequence, logits at the
    prompt's last position and at every decoded one, routes, caches)."""
    stash = []
    tapped, prefill, decode = programs_with_logits(stash)
    monkeypatch.setattr(gd, "_greedy_pick", tapped)
    fam = family_of(model)
    params, cfg = fam.decode_params(model, LMAX)
    if cfg_edit:
        cfg = cfg._replace(**cfg_edit)
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
    for _ in range(n_decode):
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
    return seq, np.stack(logits), np.concatenate(routes)[:len(seq)], caches


@pytest.mark.parametrize("plen", [5, 16, 21, 40])
def test_prefill_chunks_then_decode_give_the_reference_logits(
        config, model, monkeypatch, plen):
    """Chunked prefill (one to three chunks, lengths that are and are not
    a multiple of the chunk), then 6 decode steps through the latent cache
    with four streams and YaRN positions: the logits are the reference's
    full-forward logits and the recorded routes its own."""
    seq, logits, routes, caches = served_logits(model, monkeypatch, plen)
    want = reference_logits(config, seq, plen - 1 + np.arange(7))
    np.testing.assert_allclose(logits, want, atol=LOGIT_TOL, rtol=0)
    # the parked slot's rows are bit for bit what they were
    for (rows,) in caches:
        assert (np.asarray(rows[0]) == np.float32(0.7)).all()
    rec = np.full((1, LMAX, 2, 2), -1, np.int32)
    rec[0, :len(seq)] = routes
    st = {}
    reference_logits(config, seq, [0], routes=rec, stats=st)
    assert st["recorded"] == 2 * len(seq)
    assert st["differ"] == st["refused"] == st["followed"] == 0


@pytest.mark.parametrize("what,edit,least", [
    ("one Sinkhorn step in place of 20", dict(hc_iters=1), 0.02),
    ("the YaRN factor left out of the softmax scale",
     dict(scale_mult=1.0), 0.1),
    ("the mixing left out (H_res = I)", "identity", 0.2),
    ("H_post without its factor 2", "half_post", 0.3),
])
def test_a_departure_from_the_equations_moves_the_logits(
        config, model, monkeypatch, what, edit, least):
    """Each planted departure moves the served logits by at least 100 x
    ``LOGIT_TOL``: the tolerance is tight enough to see it."""
    if isinstance(edit, str):
        real = hyper_connection.coefficients

        def planted(X, *a, **kw):
            pre, post, res = real(X, *a, **kw)
            if edit == "half_post":
                return pre, tuple(p / 2 for p in post), res
            one, zero = jnp.ones_like(pre[0]), jnp.zeros_like(pre[0])
            return pre, post, tuple(
                tuple(one if i == j else zero for j in range(4))
                for i in range(4))

        monkeypatch.setattr(hyper_connection, "coefficients", planted)
        edit = None
    seq, logits, _, _ = served_logits(model, monkeypatch, 21, cfg_edit=edit)
    want = reference_logits(config, seq, 20 + np.arange(7))
    assert np.abs(logits - want).max() > max(least, 100 * LOGIT_TOL), what


# (d) the engine: tokens, routes, counters
def gap_under_reference(config, r):
    out = np.asarray(r.output_ids, np.int32)
    seq = np.concatenate([r.prompt_ids, out[:-1]])
    want = reference_logits(config, seq,
                            len(r.prompt_ids) - 1 + np.arange(len(out)))
    return float((want.max(-1) - want[np.arange(len(out)), out]).max())


@pytest.mark.parametrize("sync_every", [1, 3])
def test_engine_serves_the_references_tokens_and_records_routes(
        config, model, sync_every):
    """Continuous batching over 2 slots: every request carries one row of
    routes a position the programs ran, and the reference, following them,
    refuses none and departs from its own choice nowhere — ``Request
    .routes`` and the ``serving_moe_*`` counters are fed by this model's
    routes as by GLM's; nothing of the four streams reaches the engine."""
    reg = MetricsRegistry()
    eng = engine(model, sync_every=sync_every, registry=reg)
    reqs = [eng.submit(Request(p, n)) for p, n in zip(
        prompts((21, 9, 30, 16, 32, 3), seed=2), (5, 7, 4, 6, 3, 8))]
    eng.run()
    assert [r.status for r in reqs] == ["done"] * 6
    assert max(gap_under_reference(config, r) for r in reqs) <= LOGIT_TOL
    for r in reqs:
        p, n = len(r.prompt_ids), len(r.output_ids)
        rt = recorded(r)
        assert rt.shape == (p + n - 1, 2, 2) and rt.dtype == np.int8
        assert rt.min() >= 0 and rt.max() < 8
        rec = np.full((1, LMAX, 2, 2), -1, np.int32)
        rec[0, :len(rt)] = rt
        seq = np.concatenate([r.prompt_ids, r.output_ids[:-1]]).astype(
            np.int32)
        st = {}
        reference_logits(config, seq, [0], routes=rec, stats=st)
        assert (st["recorded"], st["differ"], st["refused"]) == (
            2 * len(rt), 0, 0)
    snap = reg.snapshot()
    by = lambda name, label: {s["labels"][label]: s["value"]
                              for s in snap[name]["series"]}
    rows = sum(len(recorded(r)) for r in reqs)
    total = sum(by("serving_moe_expert_tokens_total", "expert").values())
    # (the stale steps after a request retires add a few decode rows: up
    # to a dispatch of ``sync_every`` steps, one behind the pipeline)
    assert 4 * rows <= total <= 4 * (rows + 3 * sync_every * len(reqs))
    runs = by("serving_moe_dispatches_total", "program")
    touched = by("serving_moe_experts_touched_total", "program")
    assert runs["prefill"] >= 6 and runs["decode"] >= 3
    assert 2 * runs["prefill"] <= touched["prefill"] <= 16 * runs["prefill"]


def test_the_streams_never_reach_the_seam(model):
    """The four streams live inside a program run: the family states the
    same one latent rows leaf as GLM's, no state leaf, and the engine's
    caches hold rows of the latent width alone."""
    fam = family_of(model)
    params, cfg = fam.decode_params(model, LMAX)
    assert fam is gd.GLM4_MOE_LITE_FAMILY and fam.state_leaves == ()
    assert fam.rows_leaves(cfg) == RowsLeaves(1, (1, 128), 4)
    eng = engine(model)
    assert [tuple(leaf.shape) for layer in eng._kv.caches
            for leaf in layer] == [(2, LMAX, 128)] * 4
    assert fam.routed_experts(params) == 8
    assert set(params["layers"][0]) >= {"hc1_phi", "hc1_b", "hc1_alpha",
                                        "hc2_phi", "hc2_b", "hc2_alpha"}
    assert params["layers"][0]["hc1_phi"].dtype == jnp.float32


# (e) the chunks a step spends on one prompt ride in ONE run
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
    got, routes = _served_wide(model, budget, length)
    want, want_routes = _served_wide(model, 1, length)
    assert [s for s, _ in got] == ["done", "done"]
    assert got == want
    for mine, theirs in zip(routes, want_routes):
        np.testing.assert_array_equal(mine, theirs)


def test_every_width_is_compiled_by_the_first_prefill_step(model):
    wide_runs.check_warm_set(wide(model), gd._mon)


# (f) what cannot be served raises at construction, naming what is missing
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


# (g) the programs' other tenant
def test_glm_tiny_streams_are_bit_for_bit_what_they_were():
    """GLM's tiny model through the shared programs: tokens, recorded
    routes and the latent rows left in the cache are those of the commit
    before the residual path became a parameter (285947c, recorded there
    by this same drive)."""
    c = Glm4MoeLiteConfig.tiny()
    m = glm_arch.build(config_of(c), SEED, 128)
    m.eval()
    assert not [n for n, _ in m.named_parameters() if "hc" in n]
    rng = np.random.default_rng(2)
    eng = engine(m)
    reqs = [eng.submit(Request(rng.integers(1, 256, p).astype(np.int32), n))
            for p, n in zip((21, 9, 30, 16, 32, 3), (5, 7, 4, 6, 3, 8))]
    eng.run()
    assert [list(map(int, r.output_ids)) for r in reqs] == [
        [254, 84, 174, 119, 138], [6, 28, 206, 83, 100, 61, 25],
        [26, 26, 26, 26], [211, 112, 86, 86, 86, 183], [117, 59, 170],
        [113, 205, 37, 230, 200, 88, 129, 203]]
    digest = lambda arrays: hashlib.sha256(
        b"".join(np.asarray(a).tobytes() for a in arrays)).hexdigest()[:16]
    assert digest(recorded(r) for r in reqs) == "a209be70297feeb0"
    assert digest(layer[0] for layer in eng._kv.caches) == "cfaae7e2620a0047"
    assert isinstance(m, Glm4MoeLiteForCausalLM)
