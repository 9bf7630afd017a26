"""Tensor-parallel sharding for the serving hot path (GSPMD).

The engine's compiled programs (models/llama_decode.py) are pure functions
over a params pytree + KV caches, so mesh parallelism is a PLACEMENT
decision, not a code change: pick a ``PartitionSpec`` per parameter, place
the weights once, and re-``jit`` the same impl bodies with explicit in/out
shardings — XLA's SPMD partitioner inserts the collectives.  This module
owns that decision for llama serving:

* ``match_partition_rules(rules, params)`` — the fmengine/fmtrainer idiom:
  a regex → ``PartitionSpec`` table applied to the "/"-joined tree path of
  every leaf.  Scalars (and size-1 leaves) are always replicated (``PS()``);
  an unmatched non-scalar raises — silent replication of a 30B weight is
  exactly the OOM this module exists to prevent.
* ``llama_tp_rules(axis)`` — Megatron-style tensor parallelism for the
  decode params pytree: attention qkv and the MLP gate/up are COLUMN-
  parallel (output features split: ``PS(None, axis)``), the return
  projections wo/down are ROW-parallel (input features split:
  ``PS(axis, None)`` — each shard holds exactly the rows its column-
  parallel producer computed, so the only collective per layer pair is
  one psum on the residual add).  Embeddings, norms, the lm_head and the
  rope tables replicate: they are small, and a replicated lm_head keeps
  the sampled token replicated — which is what lets the host scheduler
  stay mesh-oblivious.
* ``kv_cache_pspec(axis)`` — the KV cache ``[B, Lmax, Hkv, D]`` shards
  along the HEAD axis (``PS(None, None, axis, None)``).  Decode is
  HBM-bound on KV reads (ops/decode_attention.py), and attention is
  embarrassingly parallel over heads: each chip reads only its
  ``Hkv / N`` heads — per-chip KV bytes/token drop by N, which is the
  capacity lever (the ``serving_hbm_gb_per_tok_tp`` bench column).  The
  chunked online-softmax read needs no change: its softmax/max/sum
  reductions run over the per-head chunk axis, never across heads, and
  its trip count reduces over the (replicated) lengths — head sharding
  splits only the vmapped head dimension.
* ``serving_tp_programs(...)`` — the four serving entry points re-jitted
  over the SAME impl bodies with sharded params/caches in+out, replicated
  ``cur``/``lengths``/``hist`` (the host-facing operands), and donated
  cache buffers.  Instances are cached process-wide keyed by
  (mesh, specs, statics): two engines on one mesh share compiled
  programs, exactly like the module-level single-device jits — which is
  what keeps warm sharded steps at zero retraces (``assert_no_retrace``).

Replicated-scheduler-state invariant: everything the host scheduler
touches (``cur``, ``lengths``, the spec history, emitted token blocks)
goes in and comes out replicated, so the pipelined double-buffer, chunked
prefill admission and ``_host_fetch`` drain in serving/engine.py run
UNCHANGED on a mesh — a replicated array fetches like a single-device one.
"""
from __future__ import annotations

import re

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as PS

from paddle_tpu.models.llama_decode import (
    _mon, _serving_decode_steps_impl, _serving_prefill_chunk_impl,
    _serving_spec_draft_step_impl, _serving_spec_step_impl,
)

__all__ = ["match_partition_rules", "llama_tp_rules", "kv_cache_pspec",
           "kv_scale_pspec", "kv_transfer_shardings",
           "shard_decode_params", "serving_tp_programs", "TPPrograms"]


def _path_str(path):
    """tree path entries (DictKey/SequenceKey/...) -> "layers/0/wq"."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def match_partition_rules(rules, params):
    """Map ``rules`` — an ordered ``(regex, PartitionSpec)`` table — over a
    params pytree, returning the matching PartitionSpec pytree.

    Each leaf's tree path is joined with "/" (``layers/3/wq``) and matched
    with ``re.search``; the FIRST matching rule wins, so put specific
    rules above catch-alls.  Scalar and size-1 leaves short-circuit to
    ``PS()`` (nothing to shard; rope scalars and norm epsilons never need
    rules).  A non-scalar leaf no rule matches raises ``ValueError`` —
    a new parameter must get an explicit placement decision, not a silent
    full replica on every chip."""
    def spec_of(path, leaf):
        name = _path_str(path)
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return PS()
        for rule, spec in rules:
            if re.search(rule, name):
                return spec
        raise ValueError(f"no partition rule matched param {name!r} "
                         f"with shape {tuple(shape)}")
    return jax.tree_util.tree_map_with_path(spec_of, params)


def llama_tp_rules(axis="mp"):
    """Megatron-style tensor-parallel rules for the llama decode pytree
    (module docstring has the column/row-parallel rationale)."""
    return (
        # int8 weight scales (quantize_decode_weights): a column-parallel
        # weight's [out] scale shards with its output features; a
        # row-parallel weight's scale multiplies the POST-psum product, so
        # every chip needs the whole vector — replicate.  Listed first:
        # the $-anchored weight rules below can never match "*_scale", but
        # rule order documents the pairing.
        (r"(^|/)(wq|wk|wv|gate|up)_scale$", PS(axis)),
        (r"(^|/)(wo|down)_scale$", PS()),
        # column-parallel: split output features across the mesh
        (r"(^|/)(wq|wk|wv|gate|up)$", PS(None, axis)),
        # row-parallel: split input features; psum rejoins on the residual
        (r"(^|/)(wo|down)$", PS(axis, None)),
        # small + host-facing: replicate (keeps sampled tokens replicated)
        (r"(^|/)(embed|norm|lm_head|ln1|ln2)$", PS()),
        (r"(^|/)_rope($|/)", PS()),
    )


def kv_cache_pspec(axis="mp"):
    """KV cache ``[B, Lmax, Hkv, D]`` sharded along the head axis."""
    return PS(None, None, axis, None)


def kv_scale_pspec(axis="mp"):
    """int8-cache scale array ``[B, Lmax, Hkv]`` / ``[N, C, Hkv]`` sharded
    along the head axis — the data spec minus the trailing ``D`` axis, so
    each chip holds exactly the scales for its own heads and the in-loop
    dequant stays collective-free like the data read."""
    return PS(None, None, axis)


def kv_transfer_shardings(mesh, axis="mp"):
    """Placement for migration transfer leaves (serving/disagg.py): a
    block chain's ``[n_blocks, C, Hkv, D]`` data leaves keep the head
    axis at index 2 — exactly the pool layout — so the pool specs apply
    to the transfer unchanged, and an ``InProcessTransport.send`` onto a
    TP decode worker lands each leaf already head-sharded: the splice is
    a sharded scatter with no resharding copy.  Returns ``(data_sharding,
    scale_sharding)``; pass both to the transport."""
    return (NamedSharding(mesh, kv_cache_pspec(axis)),
            NamedSharding(mesh, kv_scale_pspec(axis)))


def _tp_geometry_check(params, mesh, axis, rules=None):
    """Every sharded dimension must divide by the mesh axis size — an
    indivisible placement would silently pad on some backends and raise on
    others; fail loudly at engine construction instead."""
    n = int(mesh.shape[axis])
    specs = match_partition_rules(
        rules if rules is not None else llama_tp_rules(axis), params)
    bad = []

    def chk(path, leaf, spec):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            if int(leaf.shape[dim]) % n:
                bad.append(f"{_path_str(path)} dim {dim} "
                           f"({leaf.shape[dim]} % {n} != 0)")
    jax.tree_util.tree_map_with_path(chk, params, specs)
    if bad:
        raise ValueError(
            f"model not shardable {n}-way along mesh axis {axis!r}: "
            + "; ".join(bad))
    return specs


def shard_decode_params(params, mesh, axis="mp", rules=None):
    """Place the decode params pytree onto ``mesh`` under ``rules`` (a
    serving family's ``tp_rules(axis)``; default: the llama TP rules),
    validated for divisibility.  Returns ``(sharded_params,
    specs)`` — a one-time placement at engine construction; after it the
    sharded jits consume the weights in place with zero per-step
    transfers."""
    specs = _tp_geometry_check(params, mesh, axis, rules)
    sharded = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
    return sharded, specs


class TPPrograms:
    """The four serving entry points jitted with explicit mesh shardings.

    Statics (``cfg``, ``n_steps``, ``spec_k``, ``with_hist``,
    ``chunk_size``) are closed over — the engine fixes them at
    construction, and closing over them keeps every TP program's calling
    convention all-positional so ``in_shardings`` line up by position.
    Cache buffers are donated exactly like the single-device exports
    (plus the spec history on prefill, which the engine carries forward).
    Each wrapper dispatches through the SAME ``_mon`` program name as its
    single-device twin, so compile-cache hit/miss telemetry and
    ``assert_no_retrace`` see one program family per entry point.

    ``paged=True`` builds the block-table variants: decode/spec/pchunk
    grow one trailing replicated ``tables`` operand and the cache
    shardings apply to the ``[num_blocks, C, Hkv, D]`` pools (same
    ``kv_cache_pspec`` — the head axis is index 2 in both geometries).
    """

    def __init__(self, mesh, axis, cfg, param_specs, n_layers, *,
                 sync_every, spec_k, with_hist, chunk_size, paged=False,
                 program_key=None, dcfg=None, dparam_specs=None,
                 d_layers=0):
        repl = NamedSharding(mesh, PS())
        pshard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), param_specs,
            is_leaf=lambda x: isinstance(x, PS))
        dsh = NamedSharding(mesh, kv_cache_pspec(axis))
        quant = getattr(program_key, "kv_dtype", None) == "int8"
        ssh = NamedSharding(mesh, kv_scale_pspec(axis)) if quant else None
        # int8 caches are nested (data, scale) leaves: the sharding pytree
        # mirrors that structure, scales head-sharded on their own (3-axis)
        # spec — out_shardings extend to the scale leaf automatically
        leaf = (dsh, ssh) if quant else dsh
        cshard = [(leaf,) * 2 for _ in range(n_layers)]
        hshard = repl if with_hist else None
        self.mesh = mesh
        self.axis = axis
        self.n_devices = int(mesh.shape[axis])
        self.cache_sharding = dsh if n_layers else repl
        self.scale_sharding = ssh
        # resident draft model: its params shard under the same TP rules,
        # and its caches — whether the shared pool's first d_layers arrays
        # (paged) or the separate dense twins — keep the head axis at the
        # same index, so the target's cache leaf sharding applies verbatim
        dpshard = None
        if dparam_specs is not None:
            dpshard = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), dparam_specs,
                is_leaf=lambda x: isinstance(x, PS))
        dcshard = [(leaf,) * 2 for _ in range(d_layers)]

        if paged:
            # paged programs take one extra trailing operand: the [B, W]
            # block tables, replicated like every other host-facing array
            # (the pool itself stays head-sharded — head axis is index 2
            # in both the dense [B, Lmax, Hkv, D] and pool [N, C, Hkv, D]
            # geometries, so kv_cache_pspec applies unchanged)
            def decode(params, cur, caches, dev_lengths, tables):
                return _serving_decode_steps_impl(
                    params, cfg, cur, caches, dev_lengths,
                    n_steps=sync_every, chunk_size=chunk_size,
                    block_tables=tables, program_key=program_key)
            self.decode_steps = _mon.wrap("serving_decode_steps", jax.jit(
                decode,
                in_shardings=(pshard, repl, cshard, repl, repl),
                out_shardings=(repl, repl, cshard),
                donate_argnums=(2,)))

            def spec(params, cur, caches, dev_lengths, hist, hist_len,
                     active, tables):
                return _serving_spec_step_impl(
                    params, cfg, cur, caches, dev_lengths, hist, hist_len,
                    active, spec_k=spec_k, chunk_size=chunk_size,
                    block_tables=tables, program_key=program_key)
            self.spec_step = _mon.wrap("serving_spec_step", jax.jit(
                spec,
                in_shardings=(pshard, repl, cshard, repl, repl, repl,
                              repl, repl),
                out_shardings=(repl, repl, repl, repl, repl, cshard, repl,
                               repl)))

            if dpshard is not None:
                # draft-model speculative round over the SHARED pool: the
                # draft's k decode steps ride the first d_layers pool
                # arrays through their own block tables, then the verify
                # forward reads the full target caches — one program, no
                # host hop between draft and verify.  dcaches is None
                # (paged), so the trailing output subtree is empty and its
                # repl spec binds nothing.
                def dspec(params, dparams, cur, caches, dev_lengths,
                          active, tables, dtables):
                    return _serving_spec_draft_step_impl(
                        params, dparams, cfg, dcfg, cur, caches, None,
                        dev_lengths, active, spec_k=spec_k,
                        chunk_size=chunk_size, block_tables=tables,
                        draft_tables=dtables, program_key=program_key)
                self.spec_draft_step = _mon.wrap(
                    "serving_spec_draft_step", jax.jit(
                        dspec,
                        in_shardings=(pshard, dpshard, repl, cshard, repl,
                                      repl, repl, repl),
                        out_shardings=(repl, repl, repl, repl, repl,
                                       cshard, repl)))

                def dpchunk(params, tokens, offset, prompt_len, caches,
                            slot, tables):
                    return _serving_prefill_chunk_impl(
                        params, dcfg, tokens, offset, prompt_len, caches,
                        slot, with_hist=False, chunk_size=chunk_size,
                        block_tables=tables, program_key=program_key)
                self.draft_prefill_chunk = _mon.wrap(
                    "serving_prefill_chunk", jax.jit(
                        dpchunk,
                        in_shardings=(dpshard, repl, repl, repl, dcshard,
                                      repl, repl),
                        out_shardings=(repl, repl, dcshard, repl, repl),
                        donate_argnums=(4,)))

            def pchunk(params, tokens, offset, prompt_len, caches, slot,
                       hist, hist_len, tables):
                return _serving_prefill_chunk_impl(
                    params, cfg, tokens, offset, prompt_len, caches, slot,
                    hist=hist, hist_len=hist_len, with_hist=with_hist,
                    chunk_size=chunk_size, block_tables=tables,
                    program_key=program_key)
            self.prefill_chunk = _mon.wrap("serving_prefill_chunk", jax.jit(
                pchunk,
                in_shardings=(pshard, repl, repl, repl, cshard, repl,
                              hshard, repl, repl),
                out_shardings=(repl, repl, cshard, hshard, repl),
                donate_argnums=(4, 6) if with_hist else (4,)))
        else:
            def decode(params, cur, caches, dev_lengths):
                return _serving_decode_steps_impl(
                    params, cfg, cur, caches, dev_lengths,
                    n_steps=sync_every, chunk_size=chunk_size,
                    program_key=program_key)
            self.decode_steps = _mon.wrap("serving_decode_steps", jax.jit(
                decode,
                in_shardings=(pshard, repl, cshard, repl),
                out_shardings=(repl, repl, cshard),
                donate_argnums=(2,)))

            def spec(params, cur, caches, dev_lengths, hist, hist_len,
                     active):
                return _serving_spec_step_impl(
                    params, cfg, cur, caches, dev_lengths, hist, hist_len,
                    active, spec_k=spec_k, chunk_size=chunk_size,
                    program_key=program_key)
            self.spec_step = _mon.wrap("serving_spec_step", jax.jit(
                spec,
                in_shardings=(pshard, repl, cshard, repl, repl, repl,
                              repl),
                out_shardings=(repl, repl, repl, repl, repl, cshard, repl,
                               repl)))

            if dpshard is not None:
                # dense twin: the draft's separate [B, Lmax, Hkv, D]
                # caches travel as an explicit operand and come back
                # updated (no donation — spec programs never donate, the
                # engine re-dispatches on transient device errors)
                def dspec(params, dparams, cur, caches, dcaches,
                          dev_lengths, active):
                    return _serving_spec_draft_step_impl(
                        params, dparams, cfg, dcfg, cur, caches, dcaches,
                        dev_lengths, active, spec_k=spec_k,
                        chunk_size=chunk_size, program_key=program_key)
                self.spec_draft_step = _mon.wrap(
                    "serving_spec_draft_step", jax.jit(
                        dspec,
                        in_shardings=(pshard, dpshard, repl, cshard,
                                      dcshard, repl, repl),
                        out_shardings=(repl, repl, repl, repl, repl,
                                       cshard, dcshard)))

                def dpchunk(params, tokens, offset, prompt_len, caches,
                            slot):
                    return _serving_prefill_chunk_impl(
                        params, dcfg, tokens, offset, prompt_len, caches,
                        slot, with_hist=False, chunk_size=chunk_size,
                        program_key=program_key)
                self.draft_prefill_chunk = _mon.wrap(
                    "serving_prefill_chunk", jax.jit(
                        dpchunk,
                        in_shardings=(dpshard, repl, repl, repl, dcshard,
                                      repl),
                        out_shardings=(repl, repl, dcshard, repl, repl),
                        donate_argnums=(4,)))

            def pchunk(params, tokens, offset, prompt_len, caches, slot,
                       hist, hist_len):
                return _serving_prefill_chunk_impl(
                    params, cfg, tokens, offset, prompt_len, caches, slot,
                    hist=hist, hist_len=hist_len, with_hist=with_hist,
                    chunk_size=chunk_size, program_key=program_key)
            self.prefill_chunk = _mon.wrap("serving_prefill_chunk", jax.jit(
                pchunk,
                in_shardings=(pshard, repl, repl, repl, cshard, repl,
                              hshard, repl),
                out_shardings=(repl, repl, cshard, hshard, repl),
                donate_argnums=(4, 6) if with_hist else (4,)))


# process-wide: two engines with the same (mesh, specs, statics) must
# share compiled programs — per-engine jits would retrace per engine and
# break the warm-path zero-retrace guarantee the single-device engine has
_PROGRAMS = {}


def serving_tp_programs(mesh, axis, cfg, param_specs, n_layers, *,
                        sync_every, spec_k, with_hist, chunk_size,
                        paged=False, program_key=None, dcfg=None,
                        dparam_specs=None, d_layers=0):
    """Cached ``TPPrograms`` factory (see class docstring).

    ``program_key`` is the frozen :class:`~paddle_tpu.serving.program_key.
    ProgramKey` of static kernel/precision axes — one hashable value in
    the cache key covers every registry axis (attn_impl, prefill_impl,
    kv_dtype, weight_dtype, tp_overlap, draft_source, spec_depth,
    spec_tree), so two engines differing in any axis compile separate
    program families while identical engines share.  ``dcfg`` /
    ``dparam_specs`` / ``d_layers`` describe the resident draft model
    (draft_model source only) and fork the key like any other static.
    """
    leaves, treedef = jax.tree_util.tree_flatten(
        param_specs, is_leaf=lambda x: isinstance(x, PS))
    dleaves, dtreedef = jax.tree_util.tree_flatten(
        dparam_specs, is_leaf=lambda x: isinstance(x, PS))
    key = (mesh, axis, cfg, tuple(leaves), treedef, n_layers,
           sync_every, spec_k, with_hist, chunk_size, paged, program_key,
           dcfg, tuple(dleaves), dtreedef, d_layers)
    progs = _PROGRAMS.get(key)
    if progs is None:
        progs = _PROGRAMS[key] = TPPrograms(
            mesh, axis, cfg, param_specs, n_layers, sync_every=sync_every,
            spec_k=spec_k, with_hist=with_hist, chunk_size=chunk_size,
            paged=paged, program_key=program_key, dcfg=dcfg,
            dparam_specs=dparam_specs, d_layers=d_layers)
    return progs
