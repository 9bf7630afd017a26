"""GLM-4.7-Flash's serving programs and its serving family: the model seam's
third implementation, beside ``models/llama_decode.py`` and
``models/falcon_h1_decode.py``.  Xing4.0-29B-A4B (``models/xing4.py``) is
served by the SAME two programs: its residual path (``cfg.hc`` streams
under a hyper-connection: the state is ``[B, T, hc * hidden]`` from the
embedding to the row sum before the head, and never leaves a program run —
no cache leaf and no seam field knows of it), its softmax scale
(``cfg.scale_mult``) and its rope table (``rope_tables``) are facts of the
family's statics and parameters; with ``cfg.hc == 1`` the programs are the
plain-residual ones.

Same contracts as the Llama programs (fixed batch ``B``, static shapes,
per-slot liveness carried by the ``lengths`` operand, donated caches, the
compiled programs' names ``serving_decode_steps`` / ``serving_prefill_chunk``)
with two differences behind the seam:

- **One latent rows leaf.**  One layer's cache is the 1-tuple
  ``(rows [B, Lmax, R],)``: a token's normed latent ``c`` and its one
  shared rope key ``k_r`` (``kv_lora_rank + rope`` values, stored in whole
  128-lane tiles: ``Glm4MoeLiteStatics.row_stored``), written once, gathered once a
  chunk trip (``ops/decode_attention.py``'s ``v_width`` case), made harmless
  by a slot's length exactly like K/V rows — no ``state_leaves``.  Both
  programs run the ABSORBED attention (``models/glm4_moe_lite.py``): ``W_uk``
  folded into the query, ``W_uv`` applied to the ``kv_lora_rank``-wide
  result (scope ``mla.absorb``).
- **Recorded routes.**  Beside the tokens both programs hand back the
  experts that served each live row (``int8``; ``-1`` for a row that is not
  live: a parked slot, the padded end of a chunk): ``decode_steps`` a
  fourth result ``[B, n_steps, L_moe, k]``, ``prefill_chunk`` a sixth
  ``[P, L_moe, k]``.  The engine drains them with the tokens, counts them
  and appends them to ``Request.routes`` (``ServingFamily.routed_experts``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from paddle_tpu.models.glm4_moe_lite import (
    attn_out, ffn, mla_project, rmsnorm, rope_tables, statics_of, stream_in,
    stream_out, sublayer,
)
from paddle_tpu.models.llama_decode import _greedy_pick, _rope_at
from paddle_tpu.models.serving_family import RowsLeaves, ServingFamily
from paddle_tpu.observability.compilecache import CompileCacheMonitor
from paddle_tpu.ops.decode_attention import (
    decode_attention, slot_prefill_attention,
)
from paddle_tpu.ops.moe import live_routes

__all__ = ["GLM4_MOE_LITE_FAMILY", "serving_decode_steps",
           "serving_prefill_chunk"]

_mon = CompileCacheMonitor("glm4_moe_lite_decode")


def extract_decode_params(model):
    """The model's weights as a plain pytree of jax arrays."""
    return {
        "embed": model.model.embed_tokens.weight.data,
        "norm": model.model.norm.weight.data,
        "lm_head": model.lm_head.weight.data,
        "layers": [{k: v.data for k, v in layer.weights().items()}
                   for layer in model.model.layers],
    }


def _decode_params_of(model, lmax):
    cfg = statics_of(model.config)
    live_w = model.model.embed_tokens.weight.data
    cached = getattr(model, "_decode_cache", None)
    if cached is not None and cached[0] is live_w and cached[1] == lmax:
        _mon.hit("decode_params")
        params = cached[2]
    else:
        t0 = time.perf_counter()
        params = extract_decode_params(model)
        params["_rope"] = rope_tables(model.config, lmax,
                                      params["embed"].dtype)
        model._decode_cache = (live_w, lmax, params)
        _mon.miss("decode_params", seconds=time.perf_counter() - t0)
    return params, cfg


def init_layer_cache(cfg, batch, max_len, kv_dtype):
    """One layer's ``(rows,)``: the latent leaf alone."""
    return (jnp.zeros((batch, max_len, cfg.row_stored), kv_dtype),)


def _attend(lp, cfg, u, positions, cos_t, sin_t, attend):
    """The absorbed attention branch over normed ``u [B, T, hidden]``:
    ``attend(q [B, T, H, R], new [B, T, 1, R]) -> (o [B, T, H, rank],
    rows')`` is the cache append and read."""
    q_nope, q_rope, c, k_r = mla_project(lp, cfg, u)
    with jax.named_scope("attn.rope"):
        q_rope, k_r = _rope_at(q_rope, k_r, cos_t, sin_t, positions)
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bthd,hdc->bthc", q_nope, lp["w_uk"])
    # rows are stored in whole 128-lane tiles (cfg.row_stored): zeros
    # behind [c | k_r], and behind the query, add nothing to a score
    pad = lambda x: jnp.pad(x, [(0, 0)] * 3 + [(0, cfg.row_stored - cfg.row)])
    o_lat, rows = attend(
        pad(jnp.concatenate([q_lat, q_rope], axis=-1)),
        pad(jnp.concatenate([c[:, :, None], k_r], axis=-1)))
    with jax.named_scope("mla.absorb"):
        o = jnp.einsum("bthc,hcd->bthd", o_lat, lp["w_uv"])
    return attn_out(lp, cfg, o), rows


def _layer(lp, cfg, h, positions, cos_t, sin_t, attend, live):
    """The two residual sub-layers both programs share: h [B, T, hidden]
    (or [B, T, hc * hidden]) -> (h', rows', experts [B, T, k] or None).
    ``attend``: the program's cache append and read (``_attend``);
    ``live`` [B, T]: rows that route."""
    def attn(u):
        with jax.named_scope("norm"):
            u = rmsnorm(u, lp["ln1"], cfg.eps)
        return _attend(lp, cfg, u, positions, cos_t, sin_t, attend)

    h, rows = sublayer(lp, cfg, 1, h, attn)
    h, experts = sublayer(lp, cfg, 2, h, lambda u: ffn(lp, cfg, u, live))
    return h, rows, experts


def _layer_decode(lp, cfg, h, cache, lengths, cos_t, sin_t, chunk_size):
    """One layer over ONE new token of every slot: h [B, 1, hidden]."""
    def attend(q, new):
        # decode_attention is a jit of its own: what the compiler makes at
        # its boundary (and, for a span the read's row groups do not divide,
        # the window copies its gather becomes) keeps this CALL's path and
        # loses the scopes inside it: the scope around the call names them
        with jax.named_scope("attn.core"):
            out, rows, _, _ = decode_attention(
                q, new, None, cache[0], None, lengths, scale=cfg.scale,
                chunk_size=chunk_size, v_width=cfg.kv_rank)
        return out, rows

    live = lengths < cache[0].shape[1]
    h, rows, experts = _layer(lp, cfg, h, lengths[:, None], cos_t, sin_t,
                              attend, live[:, None])
    return h, (rows,), None if experts is None else live_routes(
        experts[:, 0], live)


def _layer_prefill(lp, cfg, h, cache, slot, offset, n_valid, cos_t, sin_t,
                   chunk_size):
    """One layer over a [1, P] prompt chunk of ``slot``; ``n_valid`` of its
    positions are real."""
    t = h.shape[1]
    positions = offset[None, None] + jnp.arange(t, dtype=jnp.int32)[None, :]

    def attend(q, new):
        out, rows, _ = slot_prefill_attention(
            q, new, None, cache[0], None, slot, offset, scale=cfg.scale,
            chunk_size=chunk_size, v_width=cfg.kv_rank)
        return out, rows

    live = jnp.arange(t, dtype=jnp.int32) < n_valid
    h, rows, experts = _layer(lp, cfg, h, positions, cos_t, sin_t, attend,
                              live[None, :])
    return h, (rows,), None if experts is None else live_routes(
        experts[0], live)


def _logits(params, cfg, h):
    """Logits of the picked rows' state h [B, hidden] (or [B, hc *
    hidden])."""
    h = stream_out(cfg, h)
    with jax.named_scope("norm"):
        h = rmsnorm(h, params["norm"], cfg.eps)
    with jax.named_scope("lm_head"):
        return (h @ params["lm_head"]).astype(jnp.float32)


def _refuse(**given):
    """The operands of the Llama programs' signatures that this family's
    programs take only at their defaults."""
    for name, value in given.items():
        if value:
            raise ValueError(
                f"glm4_moe_lite serving programs: {name} is not supported "
                "(GLM4_MOE_LITE_FAMILY.check_options refuses it at the "
                "engine's construction)")


def _serving_prefill_chunk_impl(params, cfg, tokens, offset, prompt_len,
                                caches, slot, hist=None, hist_len=None,
                                with_hist=False, chunk_size=None,
                                block_tables=None, program_key=None):
    """The next ``[1, P]`` chunk of an admitted prompt against the slot's
    latent rows — ``llama_decode._serving_prefill_chunk_impl``'s contract
    (one compiled program for every prompt length; the greedy pick at the
    prompt's last column relative to the chunk, meaningful in the final
    chunk only) plus a sixth result: the recorded routes of the run's
    rows ``int8 [P, L_moe, k]`` (``P``: one prefill chunk or the whole
    chunks of one run)."""
    _mon.mark_trace("serving_prefill_chunk")
    _refuse(with_hist=with_hist, block_tables=block_tables is not None)
    t = tokens.shape[1]
    offset = offset.astype(jnp.int32)
    slot = slot.astype(jnp.int32)
    n_valid = jnp.clip(prompt_len[0].astype(jnp.int32) - offset, 0, t)
    with jax.named_scope("embed"):
        h = stream_in(cfg, params["embed"][tokens])
    cos_t, sin_t = params["_rope"]
    new_caches, routes = [], []
    for lp, cache in zip(params["layers"], caches):
        h, cache, r = _layer_prefill(lp, cfg, h, cache, slot, offset,
                                     n_valid, cos_t, sin_t, chunk_size)
        new_caches.append(cache)
        if r is not None:
            routes.append(r)
    last_rel = jnp.clip(prompt_len - 1 - offset, 0, t - 1)      # [1]
    h = jnp.take_along_axis(h, last_rel[:, None, None], axis=1)[:, 0]
    first, ok = _greedy_pick(_logits(params, cfg, h))
    return first, ok, new_caches, hist, hist_len, jnp.stack(routes, axis=1)


serving_prefill_chunk = _mon.wrap("serving_prefill_chunk", jax.jit(
    _serving_prefill_chunk_impl,
    static_argnames=("cfg", "with_hist", "chunk_size", "program_key"),
    donate_argnames=("caches", "hist")))


def _serving_decode_steps_impl(params, cfg, cur, caches, dev_lengths,
                               n_steps=1, chunk_size=None,
                               block_tables=None, program_key=None):
    """``n_steps`` greedy tokens for every slot in ONE compiled program —
    ``llama_decode._serving_decode_steps_impl``'s contract plus a fourth
    result: the recorded routes ``int8 [B, n_steps, L_moe, k]`` of each
    step's INPUT token.  A slot whose length operand is ``Lmax`` drops its
    row writes and routes nowhere."""
    _mon.mark_trace("serving_decode_steps")
    _refuse(block_tables=block_tables is not None)
    cos_t, sin_t = params["_rope"]

    def body(carry, _):
        tok, ok, caches, lengths = carry
        with jax.named_scope("embed"):
            h = stream_in(cfg, params["embed"][tok[:, None]])
        new_caches, routes = [], []
        for lp, cache in zip(params["layers"], caches):
            h, cache, r = _layer_decode(lp, cfg, h, cache, lengths, cos_t,
                                        sin_t, chunk_size)
            new_caches.append(cache)
            if r is not None:
                routes.append(r)
        nxt, finite = _greedy_pick(_logits(params, cfg, h[:, -1]))
        return ((nxt, ok & finite, new_caches, lengths + 1),
                (nxt, jnp.stack(routes, axis=1)))

    ok0 = jnp.ones(cur.shape, bool)
    with jax.named_scope("decode.steps"):
        (_, ok, caches, _), (toks, routes) = jax.lax.scan(
            body, (cur, ok0, list(caches), dev_lengths.astype(jnp.int32)),
            None, length=n_steps)
    return toks.T, ok, caches, jnp.swapaxes(routes, 0, 1)


serving_decode_steps = _mon.wrap("serving_decode_steps", jax.jit(
    _serving_decode_steps_impl,
    static_argnames=("cfg", "n_steps", "chunk_size", "program_key"),
    donate_argnames=("caches",)))


# what the engine cannot do for this model, by engine option: the missing
# piece each message names
_MISSING = {
    "mode": "an MTP drafter (the published multi-token-prediction module "
            "is not built, and the family has no spec_step)",
    "kv_block": "a paged pool whose block holds one latent row leaf (the "
                "pool layout, prefix adoption, the host tier and the "
                "transport all assume a K and a V leaf of [.., Hkv, D])",
    "kv_dtype": "an int8 latent row (per-row scales over a row that mixes "
                "a normed latent and a rope key) and its drift budget",
    "attn_impl": "a fused cache-read kernel for one latent rows leaf",
    "prefill_impl": "a fused prefill kernel for one latent rows leaf",
    "tp_overlap": "it segments tensor-parallel matmuls, and there is no "
                  "mesh rule set",
}
_DEFAULTS = {"mode": "greedy", "kv_block": None, "kv_dtype": None,
             "attn_impl": None, "prefill_impl": None, "tp_overlap": None}


def check_options(options):
    """Refuse, at the engine's construction, every option the latent cache
    or the expert FFN has not been made to work under.  What the record
    itself says is the engine's to refuse: no ``tp_rules`` (``mesh=``), no
    ``quantize_weights`` (``weight_dtype=``)."""
    for name, default in _DEFAULTS.items():
        if options.get(name, default) != default:
            raise ValueError(
                f"ServingEngine: {name}={options[name]!r} is not supported "
                f"for a glm4_moe_lite model — missing: {_MISSING[name]}")


GLM4_MOE_LITE_FAMILY = ServingFamily(
    name="glm4_moe_lite",
    decode_params=_decode_params_of,
    rows_leaves=lambda cfg: RowsLeaves(1, (1, cfg.row_stored), cfg.heads),
    init_layer_cache=init_layer_cache,
    decode_steps=serving_decode_steps,
    prefill_chunk=serving_prefill_chunk,
    routed_experts=lambda params: int(next(
        lp["router"].shape[1] for lp in params["layers"] if "router" in lp)),
    check_options=check_options,
)
