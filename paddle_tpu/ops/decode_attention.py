"""Incremental-decode attention over a preallocated KV cache (TPU-native).

Reference parity: the phi fused ``masked_multihead_attention`` decoding op
(paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu) — one
fused append-new-kv + attend-over-cache step per generated token.

TPU-first design choices:

* **Static shapes.**  The cache is preallocated once and every decode step
  runs the SAME compiled program regardless of the current length — position
  masking (``k_idx <= cur_len``) replaces dynamic slicing.  Two cache
  geometries share that property: the DENSE layout ``[B, Lmax, Hkv, D]``
  (one contiguous row span per slot) and the PAGED layout (a global block
  pool ``[N, C, Hkv, D]`` indirected through a per-slot ``[B, Lmax/C]``
  block table — ``init_kv_pool``).  The block table is a TRACED int32
  operand, so appending a block mid-stream or remapping a slot to shared
  prefix blocks changes only operand VALUES, never shapes: zero retraces.
* **Chunked reads that follow each slot's own length.**  Decode is
  HBM-bandwidth-bound (a GEMV per head against the cache), so KV bytes ARE
  the step time — and a masked full-length read pays ``Lmax`` bytes for a
  request at context 200 in an ``Lmax=4096`` engine: 20× the traffic it
  needs.  ``chunk_size`` switches the attention read to an online-softmax
  (flash-style running max / denominator) ``lax.while_loop`` over
  ``[C]``-sized cache chunks whose trips are computed ON DEVICE from
  ``lengths`` — the compiled program is still traced exactly once (a trip
  count is a traced scalar, not a shape), and there is ONE loop a call.
  A serving batch is ragged twice over: half its slots are parked
  (``masked_lengths``) and the live contexts spread over a factor of ten.
  For the dense ``blhd`` float cache without a bias — what the serving
  engine runs by default — a trip therefore gathers chunk ``i`` of one
  block of 4-8 slots only, taken in descending order of length, and a
  chunk index gets only as many trips as hold a slot whose context
  reaches it (``_attend_chunked``): HBM traffic per step follows
  ``ceil((lengths[b] + T) / C)`` chunks for a live slot and none for a
  parked one, rounded up to the block — 1.2-1.7 × the live rows where one
  trip over all ``B`` slots up to the batch's longest live context read
  4-5 × (``kv_rows_read`` is the host-side count of the same rule; the
  engine's ``serving_kv_rows_*_total``).  Per row the recurrence is the
  same chunks in the same order, and a chunk past a row's length is
  exactly a no-op, so live rows are BITWISE those of the batch-wide loop —
  which paged and int8 caches, ``bhld``, a bias and batches of a block or
  less (a prefill chunk's one-slot view) still run, unchanged.
  ``chunk_size=None`` (default) keeps the single fused full-length read —
  still optimal when contexts sit near ``Lmax`` or the cache is small.
* **int8 cache, float math.**  ``dtype="int8"`` in ``init_kv_cache`` /
  ``init_kv_pool`` stores KV quantized (symmetric absmax over ``D``, one
  float16 scale per (position, head) row in a parallel pytree leaf) —
  quantized ON APPEND inside the same cache scatter, dequantized INSIDE
  the chunked while_loop right after each chunk read, so only int8 bytes
  (+ 2 scale bytes per row) cross HBM per chunk: ~0.53× the traffic of a
  bf16 cache.  The scale array shares every piece of the index machinery —
  ``mode="drop"`` parking, ``mode="clip"`` paged gathers, the block-table
  indirection — because its indices are the data indices minus the
  trailing ``D`` axis.  Attention math is unchanged f32.
* **Paged block indirection rides the chunked loop.**  With a
  ``block_table`` the while_loop body gathers logical chunk ``i`` of each
  row from physical pool block ``table[b, i]`` instead of slicing a dense
  row — the SAME online-softmax recurrence over the SAME ``[B, C]`` tiles
  in the same order, so a paged read is bitwise the dense chunked read of
  equal ``chunk_size`` at f32 (the serving engine's paged-vs-dense parity
  matrix pins this).  Appends route through the same table: logical
  position ``l`` lands in pool block ``table[b, l // C]`` row ``l % C``,
  and any position past the slot's mapped capacity (or a table sentinel
  ``>= N``) is routed past the pool so the scatter DROPS it — the
  write-drop parking invariant survives paging unchanged.
* **One latent rows leaf** (``v_width``).  A latent-attention cache row is
  ONE leaf ``[B, Lmax, R]`` (read as ``[B, Lmax, 1, R]``: stored with the
  unit head dim, the TPU compiler gives the leaf a position-minor layout
  and copies it whole on the way in and out — chipless compile, PR 33)
  whose first ``v_width`` values are also the values (an MLA row: the
  normed latent, then the one shared rope key).
  It is a case of the same read, not a copy of it: ``v_new`` / ``v_cache``
  are ``None``, the row is appended once and gathered once a chunk trip,
  scores run over the full row and the values are a slice of the gathered
  tile — same slot order, chunk and block rules, ``kv_rows_read`` unchanged
  (dense float ``blhd`` only).  The per-slot read's gather takes the leaf
  as ``[B * Lmax / S, S, R]``, ``S`` the rows one tile of its dtype packs
  (16 bfloat16, 8 float32: ``_tile_rows``): that is the leaf's physical
  order, so the view is free and a block's chunk is ONE gather of
  ``C / S``-group windows, as the (k, v) leaves' ``[B * Lmax, Hkv, D]``
  windows are.  Windows of the flat ``[B * Lmax, 1, R]`` view the TPU
  compiler expands into a nested loop of window copies, a slot each
  (~560 a decode run of 7 layers, latency-bound: PERF.md, PR 33 and
  PR 36); only a span or chunk that ``S`` does not divide still reads so.
* **GQA-native.**  kv heads are consumed directly (``[B, Hkv, G, ...]``
  einsums) — no ``repeat`` materialization, KV reads are 1/G of expanded
  heads.
* **Per-batch lengths.**  ``lengths [B]`` supports ragged batches (the
  reference's ``sequence_lengths``); appends use a vmapped
  ``dynamic_update_slice`` (lowers to one scatter).
* **Head-sharding safe.**  Under tensor-parallel serving
  (serving/sharding.py) the cache is sharded along the ``Hkv`` axis —
  axis 2 in BOTH geometries (dense ``[B, Lmax, Hkv, D]`` and the paged
  pool ``[N, C, Hkv, D]``), so ``kv_cache_pspec`` covers either one
  unchanged — and these reads partition cleanly: the chunked
  online-softmax running max/denominator reduce over the per-head chunk
  axis, never across heads; the slot order and the trip counts come from
  the (replicated) ``lengths``; and the per-slot row gather (dense) and
  the paged block-table gather index only the unsharded slot / pool axis
  0 with replicated indices — so GSPMD runs the
  identical program per shard on ``Hkv/N`` heads with zero cross-chip
  collectives inside the attention read.  Keep it that way: any future
  reduction ACROSS the head axis (head-mixing, cross-head norm) breaks
  the partition and must be hoisted out of this module.
* Differentiability is not a goal (decode is inference); everything here is
  plain jnp under jit.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["init_kv_cache", "init_kv_pool", "decode_attention",
           "masked_lengths", "slot_prefill_attention", "kv_rows_read"]

_LOG = logging.getLogger(__name__)
_NEG_INF = -1e30

# the supported cache storage dtypes — anything else is a loud ValueError,
# not a silent jnp.zeros coercion (a typo like "bfloat" used to surface as
# an opaque dtype error deep inside the first decode step)
_KV_DTYPES = ("float32", "float16", "bfloat16", "int8")
_Q8_MAX = 127.0
# int8 caches store a per-(position, head) float16 absmax scale alongside
# the quantized values.  float16 (not float32) keeps the analytic byte
# ratio vs a bf16 cache at (D + 2) / (2 D) — e.g. 0.53 at D=32 — instead
# of (D + 4) / (2 D); the scale magnitude is an activation absmax / 127,
# comfortably inside f16 range, and all arithmetic upcasts to f32 anyway.
_Q8_SCALE_DTYPE = jnp.float16


def _canon_dtype(dtype, where, supported, what, hint=""):
    """THE dtype-validation helper: canonicalize ``dtype`` against a
    supported-name set or raise a loud ValueError naming the set.

    ``init_kv_cache`` / ``init_kv_pool`` / the engine's ``kv_dtype`` knob
    share it via ``_canon_kv_dtype``, and the weight-quantization knob
    (models/llama_decode.py ``_canon_weight_dtype``) rides the same body —
    one canonical validation path instead of per-knob copies, so every
    storage-dtype typo fails the same way: at construction, with the
    supported set spelled out, never as an opaque dtype error deep inside
    the first compiled step."""
    try:
        name = jnp.dtype(dtype).name
    except TypeError:
        name = None
    if name not in supported:
        raise ValueError(
            f"{where}: unsupported {what} dtype {dtype!r} — supported: "
            f"{', '.join(supported)}.{hint}")
    return name


def _canon_kv_dtype(dtype, where):
    """Validate a cache dtype against the supported set -> canonical name."""
    return _canon_dtype(
        dtype, where, _KV_DTYPES, "KV cache",
        hint="  'int8' selects the quantized cache "
        "(per-(position, head) float16 scales stored in a parallel "
        "pytree leaf, quantize-on-append / dequant-in-loop).")


def _kv_data(cache):
    """Storage leaf of a cache operand: int8 caches are (data, scale)."""
    return cache[0] if isinstance(cache, tuple) else cache


def _q8_quantize(x):
    """Symmetric absmax int8 quantization over the trailing (D) axis.

    Returns (q int8 [..., D], scale f16 [...]): one scale per (position,
    head) row — the granularity that rides the cache scatter for free
    (same indices, one fewer trailing axis).  The divisor is the
    f16-ROUNDED scale, so dequantization with the stored scale reproduces
    each element to within scale/2 (+ one f16 ulp): the round-trip bound
    the unit test pins.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = (amax / _Q8_MAX).astype(_Q8_SCALE_DTYPE)
    inv = 1.0 / jnp.maximum(scale.astype(jnp.float32), 1e-8)
    q = jnp.clip(jnp.round(xf * inv[..., None]), -_Q8_MAX, _Q8_MAX)
    return q.astype(jnp.int8), scale


def _q8_dequant(q, scale):
    """Inverse of ``_q8_quantize``: f32 values from int8 data + f16 scale."""
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


def init_kv_cache(batch, max_len, num_kv_heads, head_dim, dtype="bfloat16"):
    """Preallocate a (k, v) cache pair [B, Lmax, Hkv, D].

    ``dtype="int8"`` selects the quantized cache: each of k/v becomes a
    ``(data int8 [B, Lmax, Hkv, D], scale f16 [B, Lmax, Hkv])`` pair —
    a nested pytree leaf that rides the same donated-cache plumbing, so
    the compiled serving programs specialize once on the structure and
    never retrace.
    """
    dtype = _canon_kv_dtype(dtype, "init_kv_cache")
    shape = (batch, max_len, num_kv_heads, head_dim)
    if dtype == "int8":
        def leaf():
            return (jnp.zeros(shape, jnp.int8),
                    jnp.zeros(shape[:-1], _Q8_SCALE_DTYPE))
        return leaf(), leaf()
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_kv_pool(num_blocks, block, num_kv_heads, head_dim,
                 dtype="bfloat16"):
    """Preallocate a paged (k, v) pool pair [N, C, Hkv, D].

    A slot's cache is no longer a contiguous ``[Lmax]`` row: it is the
    chain of pool blocks its ``[Lmax/C]`` block-table row names, appended
    lazily as the context grows and shareable across slots (refcounted
    prefix reuse — serving/kv_cache.py owns that bookkeeping).  The head
    axis sits at index 2 exactly like the dense cache, so the TP
    head-sharding spec applies to either geometry unchanged.

    ``dtype="int8"`` quantizes the pool: each of k/v becomes a
    ``(data int8 [N, C, Hkv, D], scale f16 [N, C, Hkv])`` pair.  The
    scale pool shares the block-table indirection — scales for logical
    chunk ``i`` live in scale block ``table[b, i]`` — so prefix sharing,
    sentinel routing, and LRU eviction all see ONE block id."""
    dtype = _canon_kv_dtype(dtype, "init_kv_pool")
    shape = (num_blocks, block, num_kv_heads, head_dim)
    if dtype == "int8":
        def leaf():
            return (jnp.zeros(shape, jnp.int8),
                    jnp.zeros(shape[:-1], _Q8_SCALE_DTYPE))
        return leaf(), leaf()
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def masked_lengths(lengths, live, lmax):
    """Per-slot write gating for continuous-batching serving.

    A serving engine runs ONE compiled step at fixed batch B while slots
    retire and are re-admitted independently.  Slots where ``live`` is
    False get offset ``lmax``: every ``_append`` index lands past the
    cache capacity so the scatter DROPS the write (mode="drop"), and the
    slot's cache/length state survives the step byte-for-byte untouched.
    Its attention output is garbage — the scheduler ignores it.

    Admission reuses the same trick with ``lengths = 0``: a prefill over
    the full batch writes ONLY the admitted slots (everyone else drops),
    so a retired slot is recycled without a reshape, a cache copy, or a
    recompile — the static-shape admission constraint on TPU.
    """
    return jnp.where(live, lengths.astype(jnp.int32), jnp.int32(lmax))


def _append(cache, new, lengths, layout, block_table=None):
    """Write ``new [B, T, Hkv, D]`` into the cache at per-batch offsets
    ``lengths [B]`` (indexed scatter — no reallocation).
    ``layout``: "blhd" cache [B, Lmax, Hkv, D] or "bhld" cache
    [B, Hkv, Lmax, D] (the reference's cache_kv layout).  With
    ``block_table [B, W]`` the cache is a paged pool [N, C, Hkv, D]
    ("blhd" only): logical position ``l`` of row ``b`` scatters into pool
    block ``table[b, l // C]`` at block row ``l % C``.

    Writes past the preallocated capacity are DROPPED (scatter
    mode="drop"), never clamped: a dynamic_update_slice would silently
    clamp the offset and overwrite the most recent valid entries (review
    r5).  The paged path preserves that contract by routing any logical
    position past the table's ``W*C`` span — and any sentinel table entry
    ``>= N`` (an unmapped chunk) — past the pool's block axis, so parked
    slots (offset ``lmax``) still drop every write.  Callers must still
    bound their decode loops by Lmax - prompt_len — an overflowing step
    simply does not extend the cache.

    An int8 ``(data, scale)`` cache quantizes ``new`` HERE — inside the
    append, not in the caller — and scatters data and scales with the SAME
    index math (the scale array is the data array minus the trailing ``D``
    axis), so drop/parking semantics hold for both leaves ("blhd" only)."""
    if isinstance(cache, tuple):
        if layout != "blhd":
            raise ValueError(
                "_append: int8 KV caches support only the blhd layout")
        data, scale = cache
        qn, sn = _q8_quantize(new)
        return (_append(data, qn, lengths, layout, block_table),
                _append(scale, sn, lengths, layout, block_table))
    lengths = lengths.astype(jnp.int32)
    if block_table is not None:
        n_blocks, c = cache.shape[0], cache.shape[1]
        b, t = new.shape[0], new.shape[1]
        w = block_table.shape[1]
        l = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        blk = jnp.take_along_axis(
            block_table.astype(jnp.int32),
            jnp.clip(l // c, 0, w - 1), axis=1)                     # [B, T]
        # invalid positions (past the W*C logical span — parked slots land
        # here) and sentinel entries route past the pool: scatter drops
        phys = jnp.where((l < w * c) & (blk < n_blocks), blk,
                         jnp.int32(n_blocks))
        return cache.at[phys.reshape(-1), (l % c).reshape(-1)].set(
            new.reshape(b * t, *new.shape[2:]).astype(cache.dtype),
            mode="drop")

    def one(c, n, off):
        # n is [T, Hkv, D] per batch entry in either cache layout
        idx = off + jnp.arange(n.shape[0], dtype=jnp.int32)
        if layout == "blhd":
            return c.at[idx].set(n.astype(c.dtype), mode="drop")
        return c.at[:, idx].set(jnp.swapaxes(n, 0, 1).astype(c.dtype),
                                mode="drop")

    return jax.vmap(one)(cache, new, lengths)


def _attend_full(qg, k_cache, v_cache, lengths, q_pos, scale, layout,
                 attn_bias, v_width=None):
    """Single fused masked read over the whole [Lmax] cache."""
    b, hkv, g, t, d = qg.shape
    if v_cache is None:         # one latent rows leaf: values = row[:v_width]
        v_cache = k_cache[..., :v_width]
    if isinstance(k_cache, tuple):
        if layout != "blhd":
            raise ValueError(
                "_attend_full: int8 KV caches support only the blhd layout")
        # full-read fallback: dequantize the whole cache (the chunked path
        # is where the bytes win lives; this keeps chunk_size=None correct)
        k_cache = _q8_dequant(*k_cache)
        v_cache = _q8_dequant(*v_cache)
    lmax = k_cache.shape[1] if layout == "blhd" else k_cache.shape[2]
    k_eq = "blkd" if layout == "blhd" else "bkld"
    s = jnp.einsum(
        f"bkgtd,{k_eq}->bkgtl", qg,
        k_cache.astype(jnp.float32), preferred_element_type=jnp.float32,
    ) * scale
    if attn_bias is not None:
        bias = jnp.asarray(attn_bias, jnp.float32)
        bias = jnp.broadcast_to(bias, (b, 1, t, lmax))
        s = s + bias[:, :, None, :, :]
    k_idx = jnp.arange(lmax, dtype=jnp.int32)
    live = k_idx[None, None, :] <= q_pos[:, :, None]                    # [B,T,L]
    s = jnp.where(live[:, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        f"bkgtl,{k_eq}->bkgtd", p, v_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32)


def _slot_block(batch):
    """Slots a trip of the per-slot read covers (the geometry the serving
    cells run: a dense ``blhd`` float cache and no bias): a block of 8
    slots, or a quarter of a batch under 32 and never fewer than 4,
    whenever the block divides a larger batch.  A function of the static
    batch alone (one compiled program a geometry).  ``None``: the whole
    batch — one trip over every slot, the batch-wide loop.

    Why these sizes (the read alone on a v5e, PERF.md PR 30): a trip costs
    about 4 us plus the bytes it gathers, and a gather of fewer slot
    windows moves its bytes more slowly (545 GB/s for the whole batch's
    slice, 450 / 345 / 263 for blocks of 8 / 4 / 2), while a larger block
    fetches more chunks that are dead for some of its slots.  32 x 2048:
    blocks of 8 read in 1.5-2.1 ms a step what 4 read in 1.9-2.1 and the
    batch-wide loop in 4.0-4.9; 16 x 4096: 4 in 1.4-1.6, 8 in 2.1-2.4,
    batch-wide 3.6-4.1; 64 x 1024 (4 KV heads): 8 in 0.8-0.9, 16 in
    0.9-1.1, batch-wide 1.2."""
    rows = min(8, max(4, batch // 4))
    return rows if batch > rows and batch % rows == 0 else None


_flat_views = set()


def _tile_rows(dtype, lmax, c):
    """Rows of ONE latent rows leaf that a trip's gather takes as a unit:
    what a tile of the leaf's dtype packs (16 for bfloat16, 8 for float32),
    so that the ``[B * Lmax / S, S, R]`` view is the leaf's physical order
    (a bitcast) and a window of ``C / S`` groups is one slot's chunk.  A
    span or a chunk the group does not divide keeps the flat
    ``[B * Lmax, 1, R]`` view (1), and says so once a process: that read
    is a loop of window copies on the chip."""
    s = 32 // jnp.dtype(dtype).itemsize
    if lmax % s == 0 and c % s == 0:
        return s
    if (lmax, c, s) not in _flat_views:
        _flat_views.add((lmax, c, s))
        _LOG.warning(
            "decode_attention: the latent rows leaf keeps its flat view "
            "(span %d, chunk %d: not whole groups of %d rows) — on a TPU "
            "a trip's read is a slot-by-slot loop of window copies "
            "(logged once per process)", lmax, c, s)
    return 1


def _chunks_needed(lengths, t, c, lmax, xp=jnp):
    """Cache chunks each slot's read needs: ``ceil((length + T) / C)`` for
    a live slot, none for one parked by ``masked_lengths`` (offset >=
    lmax).  ``xp`` is ``jax.numpy`` inside the program and ``numpy`` for
    the host's count of the same rule (``kv_rows_read``)."""
    n_chunks = -(-lmax // c)
    need = xp.minimum((lengths + (t + c - 1)) // c, n_chunks)
    return xp.where(lengths < lmax, need, 0)


def _slot_order(need, n_chunks):
    """The per-slot read's order of the slots: descending by the chunks
    they need, ties by slot index, so that chunk ``i`` is needed by the
    first ``cnt[i]`` places and by no other.  Returns ``(order [B], place
    [B], cnt [n_chunks])``: the slot at each place, each slot's place, and
    those counts.  Two small sorts and a compare of ``lengths``, the same
    for every layer of a step."""
    order = jnp.argsort(-need, stable=True).astype(jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32)
    chunk = jnp.arange(n_chunks, dtype=jnp.int32)
    cnt = jnp.sum(need[None, :] > chunk[:, None], axis=1, dtype=jnp.int32)
    return order, place, cnt


def _permute(x, index):
    """``x[index]`` along axis 0 for a permutation ``index``: in bounds by
    construction, so the gather carries no clamp and no fill."""
    return x.at[index].get(mode="promise_in_bounds")


def kv_rows_read(lengths, t, chunk, lmax, plain=True):
    """Host-side count (numpy) of the cache rows ONE layer's read touches
    for these pre-append ``lengths`` (parked slots at ``>= lmax``), by the
    rule the read itself runs (``_attend_dispatch``): the full read
    (``chunk`` None or ``>= lmax``) touches every row; the batch-wide
    chunked loop every slot up to the longest live context; the per-slot
    read (``plain``: a dense ``blhd`` float cache, no bias) blocks of
    ``_slot_block`` slots in descending order of need, only as many as
    hold a slot that needs the chunk.  Returns ``(rows read, live rows)``;
    the serving engine feeds both to its counters at every decode
    dispatch."""
    lengths = np.asarray(lengths, np.int64)
    b = lengths.shape[0]
    live = int(np.sum(np.where(lengths < lmax, lengths + t, 0)))
    if chunk is None or int(chunk) >= lmax:
        return b * lmax, live
    c = int(chunk)
    need = _chunks_needed(lengths, t, c, lmax, xp=np)
    rows = _slot_block(b) if plain else None
    if rows is None:
        return max(int(need.max(initial=0)), 1) * b * c, live
    cnt = (need[None, :] > np.arange(-(-lmax // c))[:, None]).sum(axis=1)
    return int(np.sum(-(-cnt // rows))) * rows * c, live


def _attend_chunked(qg, k_cache, v_cache, lengths, q_pos, scale, layout,
                    attn_bias, chunk, block_table=None, v_width=None):
    """Online-softmax ``lax.while_loop`` over [C]-sized cache chunks.

    ``v_cache=None``: one latent rows leaf (module docstring) — a trip
    gathers the rows once and the values are their first ``v_width``
    columns; everything else below is the same.

    Flash-style running (max, denominator, accumulator) carry; exact (not
    approximate) — the recurrence rescales previous partial sums by
    ``exp(m_old - m_new)`` so the result equals the full-read softmax up to
    float reassociation.  The trip count is a TRACED scalar: same compiled
    program every step (no retraces), but chunks past a context are never
    read — HBM traffic tracks the real contexts, not Lmax.  Slots parked by
    ``masked_lengths`` (offset >= lmax) need no chunk; their rows compute
    garbage (ignored by the scheduler) over whatever chunks they DO ride
    in, or come back 0 — finite either way.  ``lmax % C != 0`` is handled
    by clamping the tail chunk's start to ``lmax - C`` and masking the
    re-read overlap out of the tail pass.

    **Which slots a trip reads** (``_slot_block``).  A chunk that lies
    wholly past a slot's length folds in ``p = 0``, ``corr = exp(m - m) =
    1``: bit for bit nothing.  So a slot's result depends only on ITS
    chunks ``0 .. ceil((length + T) / C) - 1`` folded in order, and a trip
    is free to leave out any slot the chunk is dead for.  The per-slot
    read (dense ``blhd`` float cache, no bias) does: a trip gathers chunk
    ``i`` of one block of slots (``_slot_block``) — consecutive places of
    the descending-need order of ``_slot_order`` — folds it into those
    slots' carry rows and writes them back; a chunk index gets only as
    many trips as hold a slot that needs it.  The bytes read follow each
    slot's own length (rounded up to the chunk and the block), and every
    live row is bitwise the batch-wide loop's.  Everything else — paged,
    int8, ``bhld``, a bias, a batch the block does not divide — runs the
    SAME fold over all ``B`` slots for ``ceil((max live length + T) / C)``
    trips, as before.

    With ``block_table [B, W]`` the caches are a paged pool
    ``[N, C, Hkv, D]`` (``C == chunk``, "blhd" only): iteration ``i``
    gathers each row's chunk from pool block ``table[b, i]`` instead of
    slicing a dense row, and the logical span is ``W * C``.  Sentinel /
    stale table entries only ever name chunks past a row's live length
    (the gather CLIPS OOB indices into the pool — never the NaN-filling
    default), so the causal mask discards whatever they gather — same
    guarantee the dense path gives chunks past ``lengths[b]``.

    int8 ``(data, scale)`` caches dequantize HERE, inside the loop body,
    immediately after each chunk slice/gather — so a step moves int8
    bytes (plus 2 scale bytes per (position, head)) across HBM and the
    f32 values exist only as a [B, C] working tile.  The scale chunk uses
    the SAME start offset / block index as the data chunk (paged: the
    same ``mode="clip"`` gather), so sentinel and tail semantics are
    shared by construction.
    """
    b, hkv, g, t, d = qg.shape
    c = int(chunk)
    quant = isinstance(k_cache, tuple)
    if quant and layout != "blhd":
        raise ValueError(
            "_attend_chunked: int8 KV caches support only the blhd layout")
    k_data = _kv_data(k_cache)
    if block_table is not None:
        if layout != "blhd":
            raise ValueError(
                "paged _attend_chunked supports only the blhd layout")
        if k_data.shape[1] != c:
            raise ValueError(
                f"paged _attend_chunked: chunk ({c}) must equal the pool "
                f"block size ({k_data.shape[1]})")
        block_table = block_table.astype(jnp.int32)
        lmax = block_table.shape[1] * c
    else:
        lmax = k_data.shape[1] if layout == "blhd" else k_data.shape[2]
    n_chunks = -(-lmax // c)
    bias = None
    if attn_bias is not None:
        bias = jnp.broadcast_to(jnp.asarray(attn_bias, jnp.float32),
                                (b, 1, t, lmax))
    plain = layout == "blhd" and attn_bias is None and not quant \
        and block_table is None
    rows = _slot_block(b) if plain else None
    group = 1
    if rows is not None and v_cache is None:
        group = _tile_rows(k_data.dtype, lmax, c)
    z = jnp.int32(0)

    def read_chunk(cache, i, start, slots):
        """Chunk ``i`` ([R, Hkv, C, D]) of ``slots`` (None: every slot)."""
        if block_table is not None:
            idx = jax.lax.dynamic_slice_in_dim(block_table, i, 1,
                                               axis=1)[:, 0]        # [B]
            # mode="clip", NOT the default "fill": fill gathers NaN for a
            # sentinel/unmapped entry, and the masked softmax weight times
            # NaN is NaN — clipping reads an arbitrary REAL block whose
            # rows the causal mask zeroes exactly like dense garbage rows
            if isinstance(cache, tuple):
                blk = _q8_dequant(
                    jnp.take(cache[0], idx, axis=0, mode="clip"),
                    jnp.take(cache[1], idx, axis=0, mode="clip"))
            else:
                blk = jnp.take(cache, idx, axis=0, mode="clip")
            return jnp.swapaxes(blk, 1, 2)
        if layout != "blhd":
            return jax.lax.dynamic_slice(cache, (z, z, start, z),
                                         (b, hkv, c, d))
        if slots is not None:
            # ONE gather of the slots' windows of the [B * Lmax, Hkv, D]
            # view, which is the leaf's own order: indexing slot and
            # position apart (or slicing slot by slot) makes the TPU
            # compiler copy the whole cache into another order before the
            # loop (PERF.md, PR 29 and PR 30).  ONE latent rows leaf is
            # viewed in groups of the rows a tile packs (``_tile_rows``):
            # windows of its unit-dim view are no gather to the TPU
            # compiler but a nested loop of window copies, a slot each
            # (PERF.md, PR 36)
            view = (b * lmax // group, group * hkv, d)
            flat = cache.reshape(view)
            at = slots * lmax + start
            if group > 1:
                at = jax.lax.div(at, jnp.int32(group))
            blk = jax.lax.gather(
                flat, at[:, None],
                jax.lax.GatherDimensionNumbers(
                    offset_dims=(1, 2, 3), collapsed_slice_dims=(),
                    start_index_map=(0,)),
                (c // group,) + view[1:],
                mode="promise_in_bounds").reshape(rows, c, hkv, d)
        elif isinstance(cache, tuple):
            blk = _q8_dequant(
                jax.lax.dynamic_slice(cache[0], (z, start, z, z),
                                      (b, c, hkv, d)),
                jax.lax.dynamic_slice(cache[1], (z, start, z),
                                      (b, c, hkv)))
        else:
            blk = jax.lax.dynamic_slice(cache, (z, start, z, z),
                                        (b, c, hkv, d))
        return jnp.swapaxes(blk, 1, 2)

    def fold(i, slots, q, pos, m, l, acc):
        """Fold chunk ``i`` into the carry rows of ``slots``."""
        start = jnp.minimum(i * c, lmax - c)  # clamped tail start
        kb = read_chunk(k_cache, i, start, slots)
        vb = (kb[..., :v_width] if v_cache is None
              else read_chunk(v_cache, i, start, slots))
        s = jnp.einsum(
            "bkgtd,bkcd->bkgtc", q, kb.astype(jnp.float32),
            preferred_element_type=jnp.float32) * scale
        if bias is not None:
            bb = jax.lax.dynamic_slice(bias, (z, z, z, start), (b, 1, t, c))
            s = s + bb[:, :, None, :, :]
        k_idx = start + jnp.arange(c, dtype=jnp.int32)            # [C] global
        # causal AND not already processed (the clamped tail re-reads
        # [start, i*c) — those positions belong to the previous chunk)
        live = (k_idx[None, None, :] <= pos[:, :, None]) \
            & (k_idx >= i * c)[None, None, :]                     # [R,T,C]
        s = jnp.where(live[:, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # explicit zero on masked lanes: a fully-masked row in an executed
        # chunk has s == m_new == _NEG_INF and exp(s - m_new) == 1 — the
        # classic online-softmax pollution bug
        p = jnp.where(live[:, None, None], jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgtc,bkcd->bkgtd", p, vb.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((b, hkv, g, t), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, t), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, t, d if v_cache is not None else v_width),
                     jnp.float32)

    if rows is None:
        # highest live position + 1 this step: parked slots (>= lmax) excluded
        eff = jnp.where(lengths < lmax, lengths, 0)
        total = jnp.clip((jnp.max(eff) + t + c - 1) // c, 1, n_chunks)

        def cond(carry):
            return carry[0] < total

        def body(carry):
            i, m, l, acc = carry
            return (i + jnp.int32(1),) + fold(i, None, qg, q_pos, m, l, acc)
    else:
        order, place, cnt = _slot_order(
            _chunks_needed(lengths, t, c, lmax), n_chunks)
        # chunk indices some slot needs: cnt is non-increasing
        total = jnp.sum(cnt > 0, dtype=jnp.int32)
        # queries and carry in the order's places: a trip's rows are one
        # contiguous block of them
        q_at = _permute(qg, order)
        pos_at = _permute(q_pos, order)

        def block(x, at):
            return jax.lax.dynamic_slice_in_dim(x, at, rows, axis=0)

        def cond(carry):
            return carry[0][0] < total

        def body(carry):
            (i, at), m, l, acc = carry
            new = fold(i, block(order, at), block(q_at, at),
                       block(pos_at, at), block(m, at), block(l, at),
                       block(acc, at))
            # next block of places that holds a slot needing chunk i, else
            # the first block of chunk i + 1
            more = at + rows < jax.lax.dynamic_index_in_dim(
                cnt, i, keepdims=False)
            nxt = (jnp.where(more, i, i + 1), jnp.where(more, at + rows, z))
            return (nxt,) + tuple(
                jax.lax.dynamic_update_slice_in_dim(x, n, at, axis=0)
                for x, n in zip((m, l, acc), new))

    # the loop's own name (observability.trace.LOOPS): a %while in a
    # device trace that carries it is this cache-chunk loop
    with jax.named_scope("attn.core.chunks"):
        _, _, l, acc = jax.lax.while_loop(
            cond, body, (z if rows is None else (z, z), m0, l0, acc0))
    # a live slot's chunk 0 is always folded and position 0 is causally
    # visible to every query (q_pos >= 0), so l > 0 for any FINITE
    # attn_bias — but a bias of -inf over every visible position of a row
    # zeroes its whole denominator, and the per-slot read folds nothing
    # into a parked slot.  Guard the division so such a row comes back 0
    # (finite garbage, like the full path's softmax over all-masked
    # scores) instead of NaN.
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    if rows is not None:
        # back from the order's places to the slots
        out = _permute(out, place)
    return out


def _attend_dispatch(qg, k_cache, v_cache, lengths, q_pos, scale, layout,
                     attn_bias, chunk_size, lmax, block_table, attn_impl,
                     where, v_width=None):
    """Select the attention-read implementation for one attend.

    ``attn_impl`` (static): ``None`` / ``"reference"`` keep the existing
    dispatch — chunked ``lax.while_loop`` or fused full read — BITWISE
    unchanged; ``"pallas"`` selects the fused Pallas kernel
    (ops/paged_attention_pallas.py) when the geometry supports it and
    falls back to the reference path with a once-per-process log when it
    does not (a silent downgrade would ship while_loop speed under the
    fused flag)."""
    if attn_impl not in (None, "reference", "pallas"):
        raise ValueError(
            f"{where}: unknown attn_impl {attn_impl!r} — supported: "
            "'reference' (the lax.while_loop chunked read, the default), "
            "'pallas' (the fused paged-attention kernel, reference "
            "fallback on unsupported geometry)")
    if attn_impl == "pallas":
        from paddle_tpu.ops.paged_attention_pallas import (
            fused_decode_attention, fused_decode_supported, warn_fallback,
        )
        reason = fused_decode_supported(layout, attn_bias, chunk_size, lmax)
        if reason is None:
            return fused_decode_attention(
                qg, k_cache, v_cache, lengths, scale, int(chunk_size),
                block_table=block_table)
        warn_fallback(where, reason)
    if block_table is not None:
        return _attend_chunked(qg, k_cache, v_cache, lengths, q_pos, scale,
                               layout, attn_bias, int(chunk_size),
                               block_table)
    if chunk_size is not None and int(chunk_size) < lmax:
        return _attend_chunked(qg, k_cache, v_cache, lengths, q_pos, scale,
                               layout, attn_bias, int(chunk_size),
                               v_width=v_width)
    return _attend_full(qg, k_cache, v_cache, lengths, q_pos, scale,
                        layout, attn_bias, v_width)


def _latent_only_dense(where, v_width, v_new, v_cache, k_cache, layout,
                       block_table, impl):
    """One latent rows leaf (``v_width``) is read by the dense float
    ``blhd`` path alone: anything else is a loud error, not a wrong read."""
    if v_width is None:
        return
    if (v_new is not None or v_cache is not None or layout != "blhd"
            or isinstance(k_cache, tuple) or block_table is not None
            or impl == "pallas"):
        raise ValueError(
            f"{where}: v_width= (one latent rows leaf) takes v_new=None, "
            "v_cache=None and the dense float blhd cache with the "
            "reference read (no paging, no int8, no Pallas kernel)")


@functools.partial(jax.jit,
                   static_argnames=("scale", "layout", "chunk_size",
                                    "attn_impl", "v_width"))
def decode_attention(q, k_new, v_new, k_cache, v_cache, lengths, scale=None,
                     layout="blhd", attn_bias=None, chunk_size=None,
                     block_table=None, attn_impl=None, v_width=None):
    """One decode step: append new kv, attend causally over the cache.

    q [B, T, H, D] (T = tokens this step, usually 1); k_new/v_new
    [B, T, Hkv, D]; k_cache/v_cache per ``layout`` ("blhd"
    [B, Lmax, Hkv, D] — the model projection order — or "bhld"
    [B, Hkv, Lmax, D] — the reference cache_kv order); lengths [B] — number
    of valid cache positions BEFORE this step.  ``attn_bias`` (optional,
    broadcastable to [B, 1, T, Lmax] fp) is added to the scores (the
    reference's src_mask).  ``chunk_size`` (static) selects the
    length-adaptive chunked read (see the module docstring): HBM traffic
    follows each slot's own context instead of Lmax, allclose-identical
    to the full read; ``None`` (or >= Lmax) keeps the single fused
    full-length pass.  Returns (out [B, T, H, D], k_cache',
    v_cache', lengths + T).

    ``block_table [B, W]`` (traced int32) switches to the PAGED geometry:
    the caches are a global pool ``[N, C, Hkv, D]`` (``init_kv_pool``),
    appends and reads indirect through the table, and the logical span is
    ``W * C``.  Requires ``layout="blhd"`` and
    ``chunk_size == C`` (the chunked loop IS the paged read — see the
    module docstring); the paged read is bitwise the dense chunked read
    of the same chunk size at f32.

    Query token t (global position lengths+t) attends to cache positions
    <= lengths+t: bottom-right-aligned causality, same convention as the
    flash kernels' cached prefill.

    ``attn_impl`` (static): ``None``/``"reference"`` keep the existing
    read paths bitwise unchanged; ``"pallas"`` fuses gather + dequant +
    online softmax into one VMEM residency per KV chunk
    (ops/paged_attention_pallas.py) with reference fallback on
    unsupported geometry (logged once per process).

    ``v_width`` (static): the cache is ONE latent rows leaf (module
    docstring) — ``v_new`` and ``v_cache`` are ``None``, the values are the
    first ``v_width`` columns of a row, ``out`` is ``[B, T, H, v_width]``
    and the returned ``v_cache`` is ``None``.
    """
    b, t, h, d = q.shape
    hkv = k_new.shape[2]
    _latent_only_dense("decode_attention", v_width, v_new, v_cache, k_cache,
                       layout, block_table, attn_impl)
    if v_width is not None:
        k_cache = k_cache[:, :, None]       # the leaf is [B, Lmax, R]
    k_data = _kv_data(k_cache)
    if isinstance(k_cache, tuple) and layout != "blhd":
        raise ValueError(
            "decode_attention: int8 KV caches support only layout='blhd'")
    if block_table is not None:
        if layout != "blhd":
            raise ValueError(
                "decode_attention: paged caches support only layout='blhd'")
        if chunk_size is None or int(chunk_size) != k_data.shape[1]:
            raise ValueError(
                f"decode_attention: paged caches require chunk_size == pool "
                f"block size ({k_data.shape[1]}), got {chunk_size}")
        lmax = block_table.shape[1] * k_data.shape[1]
    else:
        lmax = k_data.shape[1] if layout == "blhd" else k_data.shape[2]
    if hkv <= 0 or h % hkv:
        raise ValueError(
            f"decode_attention: query heads ({h}) must be an integer "
            f"multiple of kv heads ({hkv})")
    g = h // hkv
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    lengths = lengths.astype(jnp.int32)

    with jax.named_scope("attn.kv_write"):
        k_cache = _append(k_cache, k_new, lengths, layout, block_table)
        if v_width is None:
            v_cache = _append(v_cache, v_new, lengths, layout, block_table)

    with jax.named_scope("attn.core"):
        qg = q.reshape(b, t, hkv, g, d).transpose(0, 2, 3, 1, 4) \
            .astype(jnp.float32)                            # [B,Hkv,G,T,D]
        q_pos = lengths[:, None] \
            + jnp.arange(t, dtype=jnp.int32)[None, :]       # [B,T]
        out = _attend_dispatch(qg, k_cache, v_cache, lengths, q_pos, scale,
                               layout, attn_bias, chunk_size, lmax,
                               block_table, attn_impl, "decode_attention",
                               v_width)
        out = out.transpose(0, 3, 1, 2, 4) \
            .reshape(b, t, h, d if v_width is None else v_width) \
            .astype(q.dtype)
    if v_width is not None:
        k_cache = k_cache[:, :, 0]
    return out, k_cache, v_cache, lengths + t


def _prefill_dispatch(q, k_new, v_new, k_cache, v_cache, slot, offset,
                      scale, chunk_size, lmax, block_table, prefill_impl,
                      where):
    """Select the prefill implementation for one admission chunk.

    ``prefill_impl`` (static): ``None`` / ``"reference"`` keep the
    existing scatter + chunked-read path BITWISE unchanged (return
    ``None`` so the caller runs it); ``"pallas"`` selects the fused
    attention + quantize-on-append kernel
    (ops/prefill_attention_pallas.py) when the geometry supports it and
    falls back with a once-per-process (call-site, reason) log when it
    does not — a prefill downgrade is keyed separately from any decode
    downgrade, so neither silences the other."""
    if prefill_impl not in (None, "reference", "pallas"):
        raise ValueError(
            f"{where}: unknown prefill_impl {prefill_impl!r} — supported: "
            "'reference' (scatter + chunked read, the default), 'pallas' "
            "(the fused prefill-attention + KV-append kernel, reference "
            "fallback on unsupported geometry)")
    if prefill_impl != "pallas":
        return None
    from paddle_tpu.ops.prefill_attention_pallas import (
        fused_prefill_attention, fused_prefill_supported,
    )
    from paddle_tpu.ops.paged_attention_pallas import warn_fallback
    t = q.shape[1]
    reason = fused_prefill_supported(chunk_size, lmax,
                                     t, block_table is not None)
    if reason is None:
        # ONE kernel appends the chunk's rows and attends over them: its
        # time is attn.core's (no separate attn.kv_write exists here)
        with jax.named_scope("attn.core"):
            return fused_prefill_attention(
                q, k_new, v_new, k_cache, v_cache, slot, offset, scale,
                int(chunk_size), block_table=block_table)
    warn_fallback(where, f"prefill: {reason}", knob="prefill_impl")
    return None


def slot_prefill_attention(q, k_new, v_new, k_cache, v_cache, slot, offset,
                           scale=None, chunk_size=None, block_table=None,
                           attn_impl=None, prefill_impl=None, v_width=None):
    """``_slot_prefill_attention`` (below: the contract), jitted as
    ``decode_attention`` is: a prefill program's layers call it at one set
    of shapes, so its body is traced and lowered ONCE a program and not
    once a layer — half of what tracing a 16-layer prefill program costs,
    and a serving engine traces one such program a run width in its set-up
    (PERF.md, PR 34).  The call sits under ``attn.core``: what the compiler
    makes at the call's boundary keeps only the call's own path, and the
    scopes inside still name everything else."""
    with jax.named_scope("attn.core"):
        return _slot_prefill_attention(
            q, k_new, v_new, k_cache, v_cache, slot, offset, scale=scale,
            chunk_size=chunk_size, block_table=block_table,
            attn_impl=attn_impl, prefill_impl=prefill_impl, v_width=v_width)


@functools.partial(jax.jit,
                   static_argnames=("scale", "chunk_size", "attn_impl",
                                    "prefill_impl", "v_width"))
def _slot_prefill_attention(q, k_new, v_new, k_cache, v_cache, slot, offset,
                            scale=None, chunk_size=None, block_table=None,
                            attn_impl=None, prefill_impl=None, v_width=None):
    """Chunked-prefill attention for ONE slot of the batch cache.

    The serving engine's chunked admission path processes a prompt in
    fixed-size ``[1, P]`` pieces against the SLOT'S rows of the shared
    ``[B, Lmax]`` batch cache — not against a fresh per-bucket mini cache —
    so one compiled program covers every prompt length.  ``slot`` and
    ``offset`` are TRACED scalars (the device-carried write cursor): the
    chunk's k/v rows are scattered into cache row ``slot`` at positions
    ``offset + i`` (rows past capacity DROP, never clamp — same contract as
    ``_append``), and the chunk's queries attend causally over the slot's
    written prefix: query i (global position ``offset + i``) sees every
    previously written row ``< offset`` plus the intra-chunk causal prefix
    ``<= offset + i`` — exactly the monolithic prefill's mask restricted to
    this chunk's query rows, so chaining the chunks reproduces the
    monolithic forward.  Tail-chunk pad rows land in the cache as garbage
    at positions ``>= prompt_len`` — causally invisible to every real
    query and overwritten by decode appends, the same invariant the
    monolithic bucket-pad path relies on.

    ``chunk_size`` selects the length-adaptive chunked read over the
    slot's row (trip count tracks ``offset + P``, not ``Lmax``); ``None``
    keeps the fused full-length read.  Only the ``blhd`` layout (the
    model projection order the serving path uses) is supported.

    ``block_table [B, W]`` (traced int32) switches to the PAGED geometry:
    the caches are a pool ``[N, C, Hkv, D]`` and the chunk's rows scatter
    and read through the SLOT'S table row (gathered by the traced
    ``slot``), so no dense per-slot view is materialized.  Requires
    ``chunk_size == C``, like ``decode_attention``.

    ``prefill_impl`` (static): ``None``/``"reference"`` keep the
    scatter + chunked-read path bitwise unchanged; ``"pallas"`` fuses
    the chunk's attention WITH its quantize-on-append into one Pallas
    kernel (ops/prefill_attention_pallas.py) when the geometry supports
    it, reference fallback (logged once per process per reason)
    otherwise.  ``attn_impl`` keeps selecting the cache-READ kernel on
    the reference path.

    ``v_width``: ONE latent rows leaf, as in ``decode_attention``
    (``v_new`` / ``v_cache`` ``None``; ``out`` ``[1, P, H, v_width]``).

    q [1, P, H, D]; k_new/v_new [1, P, Hkv, D]; caches [B, Lmax, Hkv, D].
    Returns (out [1, P, H, D], k_cache', v_cache').
    """
    b, t, h, d = q.shape
    _latent_only_dense("slot_prefill_attention", v_width, v_new, v_cache,
                       k_cache, "blhd", block_table,
                       "pallas" if "pallas" in (attn_impl, prefill_impl)
                       else None)
    if v_width is not None:
        k_cache = k_cache[:, :, None]       # the leaf is [B, Lmax, R]
    if b != 1:
        raise ValueError(
            f"slot_prefill_attention: chunk batch must be 1 (got {b})")
    hkv = k_new.shape[2]
    lmax = _kv_data(k_cache).shape[1]
    if hkv <= 0 or h % hkv:
        raise ValueError(
            f"slot_prefill_attention: query heads ({h}) must be an integer "
            f"multiple of kv heads ({hkv})")
    g = h // hkv
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    slot = slot.astype(jnp.int32) if hasattr(slot, "astype") \
        else jnp.int32(slot)
    offset = offset.astype(jnp.int32) if hasattr(offset, "astype") \
        else jnp.int32(offset)

    if block_table is not None:
        blk = _kv_data(k_cache).shape[1]
        if chunk_size is None or int(chunk_size) != blk:
            raise ValueError(
                f"slot_prefill_attention: paged caches require "
                f"chunk_size == kv_block (the pool block size): got "
                f"chunk_size={chunk_size!r} with kv_block={blk} — the "
                "chunked loop IS the paged read, so the read chunk and "
                "the pool block must coincide")
        w = block_table.shape[1]
        # the slot's [1, W] table row (slot < B: no clamping)
        trow = jax.lax.dynamic_slice(
            block_table.astype(jnp.int32), (slot, jnp.int32(0)), (1, w))
        fused = _prefill_dispatch(
            q, k_new, v_new, k_cache, v_cache, slot, offset, scale,
            int(chunk_size), w * blk, trow, prefill_impl,
            "slot_prefill_attention")
        if fused is not None:
            return fused
        with jax.named_scope("attn.kv_write"):
            k_cache = _append(k_cache, k_new, offset[None], "blhd", trow)
            v_cache = _append(v_cache, v_new, offset[None], "blhd", trow)
        with jax.named_scope("attn.core"):
            qg = q.reshape(1, t, hkv, g, d).transpose(0, 2, 3, 1, 4) \
                .astype(jnp.float32)
            q_pos = offset[None, None] \
                + jnp.arange(t, dtype=jnp.int32)[None, :]
            out = _attend_dispatch(qg, k_cache, v_cache, offset[None], q_pos,
                                   scale, "blhd", None, int(chunk_size),
                                   w * blk, trow, attn_impl,
                                   "slot_prefill_attention")
            out = out.transpose(0, 3, 1, 2, 4).reshape(1, t, h, d) \
                .astype(q.dtype)
        return out, k_cache, v_cache

    fused = _prefill_dispatch(
        q, k_new, v_new, k_cache, v_cache, slot, offset, scale,
        chunk_size, lmax, None, prefill_impl, "slot_prefill_attention")
    if fused is not None:
        return fused

    # scatter the chunk's rows into the slot (drop past capacity); int8
    # caches quantize the chunk here and scatter data + scales at the
    # same (slot, row) indices
    rows = offset + jnp.arange(t, dtype=jnp.int32)
    batch_idx = jnp.full((t,), slot, jnp.int32)

    def scatter(cache, new):
        if isinstance(cache, tuple):
            qn, sn = _q8_quantize(new[0])
            return (cache[0].at[batch_idx, rows].set(qn, mode="drop"),
                    cache[1].at[batch_idx, rows].set(sn, mode="drop"))
        return cache.at[batch_idx, rows].set(
            new[0].astype(cache.dtype), mode="drop")

    with jax.named_scope("attn.kv_write"):
        k_cache = scatter(k_cache, k_new)
        if v_width is None:
            v_cache = scatter(v_cache, v_new)

    # the slot's [1, Lmax] view (slot < B: no dynamic_slice clamping)
    def slot_view(cache):
        if isinstance(cache, tuple):
            return (jax.lax.dynamic_slice(
                        cache[0], (slot, jnp.int32(0), jnp.int32(0),
                                   jnp.int32(0)), (1, lmax, hkv, d)),
                    jax.lax.dynamic_slice(
                        cache[1], (slot, jnp.int32(0), jnp.int32(0)),
                        (1, lmax, hkv)))
        return jax.lax.dynamic_slice(
            cache, (slot, jnp.int32(0), jnp.int32(0), jnp.int32(0)),
            (1, lmax, hkv, d))

    with jax.named_scope("attn.core"):
        ks = slot_view(k_cache)
        vs = slot_view(v_cache) if v_width is None else None
        qg = q.reshape(1, t, hkv, g, d).transpose(0, 2, 3, 1, 4) \
            .astype(jnp.float32)                            # [1,Hkv,G,T,D]
        q_pos = offset[None, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        lengths = offset[None]                              # [1]
        out = _attend_dispatch(qg, ks, vs, lengths, q_pos, scale, "blhd",
                               None, chunk_size, lmax, None, attn_impl,
                               "slot_prefill_attention", v_width)
        out = out.transpose(0, 3, 1, 2, 4) \
            .reshape(1, t, h, d if v_width is None else v_width) \
            .astype(q.dtype)
    if v_width is not None:
        k_cache = k_cache[:, :, 0]
    return out, k_cache, v_cache
