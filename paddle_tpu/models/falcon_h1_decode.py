"""Falcon-H1's serving programs and its serving family: the model seam's
second implementation, beside ``models/llama_decode.py``.

Same contracts as the Llama programs (fixed batch ``B``, static shapes,
per-slot liveness carried by the ``lengths`` operand, donated caches, the
compiled programs' names ``serving_decode_steps`` / ``serving_prefill_chunk``)
with a second kind of per-slot state beside the K/V rows.  One layer's cache
is ``(k, v, ssm, conv)``:

- ``k``, ``v`` ``[B, Lmax, Hkv, D]``: the attention branch's rows, made
  harmless by a slot's length exactly as in the Llama programs (the branch
  IS ``ops/decode_attention.py``);
- ``ssm`` ``[B, H, P, N]`` float32: the recurrent state, accumulated over
  every token of a request;
- ``conv`` ``[B, K-1, C]``: the last ``K - 1`` inputs of the depthwise
  convolution (the *tail*).

A length does not make the last two harmless, so the programs keep these
invariants (``tests/test_falcon_h1_serving.py`` holds each):

- **reset**: the prefill-chunk program takes zeros in the state's place when
  the chunk's ``offset`` is 0 — a request's first chunk, no extra dispatch
  (a select, not a multiply: a poisoned state's NaN does not survive it);
- **carry**: every later chunk reads what the one before wrote;
- **padding**: positions of the last chunk at or past ``prompt_len`` run with
  ``dt = 0`` (decay 1, nothing added) and the new tail is cut after the last
  real input, so the padded end advances neither;
- **parking**: the decode program keeps the state and the tail of every slot
  whose length operand is ``Lmax`` (``masked_lengths``: freed slots and slots
  mid-prefill) bit for bit; the state update (``ops.ssm.ssm_state_update``)
  never reads or writes a parked slot's state.  The pipeline's one-step-late stale step may
  still update a slot that was just retired; the next tenant's first chunk
  is dispatched after it in device program order and resets the slot.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from paddle_tpu.models.falcon_h1 import (
    attn_out, attn_qkv, embed, mlp, rmsnorm, ssm_head_params, ssm_in,
    ssm_out, ssm_split, statics_of,
)
from paddle_tpu.models.llama_decode import (
    _greedy_pick, _rope_at, _rope_tables,
)
from paddle_tpu.models.serving_family import (
    RowsLeaves, ServingFamily, StateLeaf,
)
from paddle_tpu.observability.compilecache import CompileCacheMonitor
from paddle_tpu.ops.decode_attention import (
    decode_attention, init_kv_cache, slot_prefill_attention,
)
from paddle_tpu.ops.ssm import (
    causal_conv1d, conv_tail_after, ssd_chunked, ssm_state_update,
)

__all__ = ["FALCON_H1_FAMILY", "serving_decode_steps",
           "serving_prefill_chunk"]

_mon = CompileCacheMonitor("falcon_h1_decode")

SSM_STATE_DTYPE = jnp.float32
# test-only seam, read when a program is traced (like serving/faults.py's):
# False plants the fault "the state reset at admission is skipped", under
# which the benchmark's ``correct`` has to come out false
_RESET_AT_ADMISSION = True


def extract_decode_params(model):
    """The model's weights as a plain pytree of jax arrays."""
    return {
        "embed": model.model.embed_tokens.weight.data,
        "norm": model.model.final_layernorm.weight.data,
        "lm_head": model.lm_head.weight.data,
        "layers": [{k: v.data for k, v in blk.weights().items()}
                   for blk in model.model.layers],
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
        params["_rope"] = _rope_tables(lmax, cfg.head_dim,
                                       float(model.config.rope_theta),
                                       params["embed"].dtype)
        model._decode_cache = (live_w, lmax, params)
        _mon.miss("decode_params", seconds=time.perf_counter() - t0)
    return params, cfg


def init_layer_cache(cfg, batch, max_len, kv_dtype):
    """One layer's ``(k, v, ssm, conv)``."""
    k, v = init_kv_cache(batch, max_len, cfg.kv_heads, cfg.head_dim, kv_dtype)
    return (k, v,
            jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state),
                      SSM_STATE_DTYPE),
            jnp.zeros((batch, cfg.d_conv - 1, cfg.conv_channels), k.dtype))


def _grouped(cfg, ssm):
    """The state leaf ``[.., H, P, N]`` with its heads by group
    ``[.., G, E, P, N]`` (a reshape of leading dims: no copy)."""
    return ssm.reshape(*ssm.shape[:-3], cfg.groups,
                       cfg.ssm_heads // cfg.groups, *ssm.shape[-2:])


def _layer_decode(lp, cfg, h, cache, lengths, cos_t, sin_t, chunk_size):
    """One block over ONE new token of every slot: h [B, 1, hidden]."""
    kc, vc, ssm, conv = cache
    live = lengths < kc.shape[1]
    with jax.named_scope("norm"):
        u = rmsnorm(h, lp["ln1"], cfg.eps)
    q, k, v = attn_qkv(lp, cfg, u)
    with jax.named_scope("attn.rope"):
        q, k = _rope_at(q, k, cos_t, sin_t, lengths[:, None])
    out, kc, vc, _ = decode_attention(q, k, v, kc, vc, lengths,
                                      chunk_size=chunk_size)
    a = attn_out(lp, cfg, out)

    z, xbc, dt = ssm_in(lp, cfg, u)
    xbc, xx = causal_conv1d(xbc, conv, lp["conv_w"], lp["conv_b"])
    with jax.named_scope("ssm.conv"):
        conv = jnp.where(live[:, None, None], xx[:, 1:], conv)
    x, bm, cm = ssm_split(cfg, xbc[:, 0])
    a_neg, d = ssm_head_params(lp, cfg)
    y, new = ssm_state_update(x, dt[:, 0].reshape(-1, *a_neg.shape), a_neg,
                              bm, cm, d, _grouped(cfg, ssm), live)
    m = ssm_out(lp, cfg, y[:, None], z)
    return mlp(lp, cfg, h + a + m), (kc, vc, new.reshape(ssm.shape), conv)


def _layer_prefill(lp, cfg, h, cache, slot, offset, n_valid, cos_t, sin_t,
                   chunk_size):
    """One block over a [1, P] prompt chunk of ``slot``; ``n_valid`` of its
    positions are real."""
    kc, vc, ssm, conv = cache
    t = h.shape[1]
    zero = jnp.int32(0)
    with jax.named_scope("norm"):
        u = rmsnorm(h, lp["ln1"], cfg.eps)
    q, k, v = attn_qkv(lp, cfg, u)
    with jax.named_scope("attn.rope"):
        positions = offset[None, None] \
            + jnp.arange(t, dtype=jnp.int32)[None, :]
        q, k = _rope_at(q, k, cos_t, sin_t, positions)
    out, kc, vc = slot_prefill_attention(q, k, v, kc, vc, slot, offset,
                                         chunk_size=chunk_size)
    a = attn_out(lp, cfg, out)

    z, xbc, dt = ssm_in(lp, cfg, u)
    first = (offset == 0) & _RESET_AT_ADMISSION
    with jax.named_scope("ssm.conv"):
        tail = jax.lax.dynamic_slice_in_dim(conv, slot, 1, axis=0)
        tail = jnp.where(first, jnp.zeros_like(tail), tail)
    xbc, xx = causal_conv1d(xbc, tail, lp["conv_w"], lp["conv_b"])
    tail = conv_tail_after(xx, n_valid, cfg.d_conv)
    with jax.named_scope("ssm.conv"):
        conv = jax.lax.dynamic_update_slice(conv, tail.astype(conv.dtype),
                                            (slot, zero, zero))
    x, bm, cm = ssm_split(cfg, xbc[0])
    a_neg, d = ssm_head_params(lp, cfg)
    with jax.named_scope("ssm.scan"):
        s0 = jax.lax.dynamic_slice_in_dim(ssm, slot, 1, axis=0)
        s0 = jnp.where(first, jnp.zeros_like(s0), s0)
        valid = jnp.arange(t, dtype=jnp.int32) < n_valid
        dt = jnp.where(valid[:, None], dt[0], 0.0)
    y, s1 = ssd_chunked(x, dt.reshape(t, *a_neg.shape), a_neg, bm, cm, d,
                        _grouped(cfg, s0[0]), cfg.chunk)
    with jax.named_scope("ssm.scan"):
        ssm = jax.lax.dynamic_update_slice(
            ssm, s1.reshape(1, *ssm.shape[1:]), (slot, zero, zero, zero))
    m = ssm_out(lp, cfg, y[None], z)
    return mlp(lp, cfg, h + a + m), (kc, vc, ssm, conv)


def _logits(params, cfg, h):
    with jax.named_scope("norm"):
        h = rmsnorm(h, params["norm"], cfg.eps)
    with jax.named_scope("lm_head"):
        return (h @ params["lm_head"]).astype(jnp.float32) * cfg.head_mult


def _refuse(**given):
    """The operands of the Llama programs' signatures that this family's
    programs take only at their defaults."""
    for name, value in given.items():
        if value:
            raise ValueError(
                f"falcon_h1 serving programs: {name} is not supported "
                "(FALCON_H1_FAMILY.check_options refuses it at the "
                "engine's construction)")


def _serving_prefill_chunk_impl(params, cfg, tokens, offset, prompt_len,
                                caches, slot, hist=None, hist_len=None,
                                with_hist=False, chunk_size=None,
                                block_tables=None, program_key=None):
    """The next ``[1, P]`` chunk of an admitted prompt against the slot's
    rows and state — ``llama_decode._serving_prefill_chunk_impl``'s
    contract (one compiled program for every prompt length; the greedy pick
    at the prompt's last column relative to the chunk, meaningful in the
    final chunk only), plus the state's reset, carry and padding rules of
    this module's docstring.  ``P`` must be a multiple of
    ``mamba_chunk_size`` (one prefill chunk or the whole chunks of one
    run: the scan carries the state across them)."""
    _mon.mark_trace("serving_prefill_chunk")
    _refuse(with_hist=with_hist, block_tables=block_tables is not None)
    t = tokens.shape[1]
    offset = offset.astype(jnp.int32)
    slot = slot.astype(jnp.int32)
    n_valid = jnp.clip(prompt_len[0].astype(jnp.int32) - offset, 0, t)
    with jax.named_scope("embed"):
        h = embed(params["embed"], tokens, cfg)
    cos_t, sin_t = params["_rope"]
    new_caches = []
    for lp, cache in zip(params["layers"], caches):
        h, cache = _layer_prefill(lp, cfg, h, cache, slot, offset, n_valid,
                                  cos_t, sin_t, chunk_size)
        new_caches.append(cache)
    last_rel = jnp.clip(prompt_len - 1 - offset, 0, t - 1)      # [1]
    h = jnp.take_along_axis(h, last_rel[:, None, None], axis=1)[:, 0]
    first, ok = _greedy_pick(_logits(params, cfg, h))
    return first, ok, new_caches, hist, hist_len


serving_prefill_chunk = _mon.wrap("serving_prefill_chunk", jax.jit(
    _serving_prefill_chunk_impl,
    static_argnames=("cfg", "with_hist", "chunk_size", "program_key"),
    donate_argnames=("caches", "hist")))


def _serving_decode_steps_impl(params, cfg, cur, caches, dev_lengths,
                               n_steps=1, chunk_size=None,
                               block_tables=None, program_key=None):
    """``n_steps`` greedy tokens for every slot in ONE compiled program —
    ``llama_decode._serving_decode_steps_impl``'s contract.  A slot whose
    length operand is ``Lmax`` drops its K/V writes AND keeps its state and
    tail."""
    _mon.mark_trace("serving_decode_steps")
    _refuse(block_tables=block_tables is not None)
    cos_t, sin_t = params["_rope"]

    def body(carry, _):
        tok, ok, caches, lengths = carry
        with jax.named_scope("embed"):
            h = embed(params["embed"], tok[:, None], cfg)
        new_caches = []
        for lp, cache in zip(params["layers"], caches):
            h, cache = _layer_decode(lp, cfg, h, cache, lengths, cos_t,
                                     sin_t, chunk_size)
            new_caches.append(cache)
        nxt, finite = _greedy_pick(_logits(params, cfg, h[:, -1]))
        return (nxt, ok & finite, new_caches, lengths + 1), nxt

    ok0 = jnp.ones(cur.shape, bool)
    with jax.named_scope("decode.steps"):
        (_, ok, caches, _), toks = jax.lax.scan(
            body, (cur, ok0, list(caches), dev_lengths.astype(jnp.int32)),
            None, length=n_steps)
    return toks.T, ok, caches


serving_decode_steps = _mon.wrap("serving_decode_steps", jax.jit(
    _serving_decode_steps_impl,
    static_argnames=("cfg", "n_steps", "chunk_size", "program_key"),
    donate_argnames=("caches",)))


# what the engine cannot do for a model with recurrent state, by engine
# option: the missing piece each message names
_MISSING = {
    "mode": "rejected drafts must roll the recurrent state back (a state "
            "snapshot per verify round)",
    "kv_block": "prefix adoption, the host tier, adopt_prefilled and "
                "preemption reuse K/V blocks, and a block carries no "
                "recurrent state: they need a state snapshot per block "
                "boundary",
    "kv_dtype": "an int8 K/V drift budget measured for this model",
    "attn_impl": "the fused cache-read kernel has not been run under this "
                 "model's programs",
    "prefill_impl": "the fused prefill kernel has not been run under this "
                    "model's programs",
    "tp_overlap": "it segments tensor-parallel matmuls, and there is no "
                  "mesh rule set",
}
_DEFAULTS = {"mode": "greedy", "kv_block": None, "kv_dtype": None,
             "attn_impl": None, "prefill_impl": None, "tp_overlap": None}


def check_options(options):
    """Refuse, at the engine's construction, every option under which the
    recurrent state would be silently wrong or has not been made to work.
    What the record itself says is the engine's to refuse: no ``tp_rules``
    (``mesh=``), no ``quantize_weights`` (``weight_dtype=``)."""
    for name, default in _DEFAULTS.items():
        if options.get(name, default) != default:
            raise ValueError(
                f"ServingEngine: {name}={options[name]!r} is not supported "
                f"for a falcon_h1 model — missing: {_MISSING[name]}")
    pchunk, chunk = options["prefill_chunk"], options["cfg"].chunk
    if pchunk % chunk:
        raise ValueError(
            f"ServingEngine: prefill_chunk ({pchunk}, after the clamp to "
            f"max_len) must be a multiple of mamba_chunk_size ({chunk}): "
            "the chunked scan runs whole SSD chunks")


FALCON_H1_FAMILY = ServingFamily(
    name="falcon_h1",
    decode_params=_decode_params_of,
    rows_leaves=lambda cfg: RowsLeaves(2, (cfg.kv_heads, cfg.head_dim),
                                       cfg.heads),
    init_layer_cache=init_layer_cache,
    decode_steps=serving_decode_steps,
    prefill_chunk=serving_prefill_chunk,
    state_leaves=(
        StateLeaf("ssm_state", 2, "zeros taken in its place inside the "
                  "request's first prefill chunk (offset 0)"),
        StateLeaf("conv_tail", 3, "the same"),
    ),
    check_options=check_options,
)
