"""KV-cache + slot state machine for the serving engine.

Extracted from serving/engine.py so placement policies (tensor-parallel
head sharding today, paged block tables next — ROADMAP item 2) plug in
underneath the scheduler without re-threading it.  The manager owns the
three per-slot facts the engine's scheduling logic reads and the device
programs consume:

* ``caches`` — the per-layer ``(k, v)`` pytrees, ``[B, Lmax, Hkv, D]``
  each, preallocated once (ops.decode_attention.init_kv_cache) and
  thereafter only REBOUND by the engine to each dispatch's donated
  outputs.  With ``sharding`` set (a ``NamedSharding`` over the head
  axis — serving/sharding.kv_cache_pspec) the zeros are placed sharded
  at construction, so every later donated output inherits the layout and
  no per-step resharding ever happens.
* ``lengths`` — the host int32 mirror of each slot's device write offset
  (prompt + emitted so far).  The engine bumps it as dispatches go out;
  ``device_lengths`` masks it through
  ops.decode_attention.masked_lengths, which parks every dead slot at
  ``max_len`` so its cache writes DROP — retirement needs no reshape,
  copy-out, or recompile (the write-drop parking invariant).
* ``reqs`` — slot -> live Request (None = free).  Slot allocation is
  lowest-free-first; the engine compares stored Request objects by
  identity at drain time to discard stale pipelined tokens, so the
  manager never recycles state, only the slot index.

``PagedKVCacheManager`` swaps the dense per-slot rows for a global block
pool (ops.decode_attention.init_kv_pool) indirected through per-slot
block tables — the paged geometry of ROADMAP item 2:

* blocks are REFCOUNTED: a radix map keyed on token-id chunks lets
  multiple slots map the same physical prefix blocks (decode only
  appends PAST the shared prefix, so copy-on-write is unnecessary).
  The key is TOKEN IDS, never cache bytes — so prefix reuse is
  storage-dtype-agnostic: an int8 (data, scale) pool shares blocks by
  the same table ids, one block id covering both leaves;
* refcount-0 blocks that still back a cached prefix stay resident as
  EVICTABLE until the allocator needs them (LRU-first subtree
  eviction), so an identical prompt admitted later skips its prefill;
* the table rows are host int32 mirrors shipped to the device as
  TRACED operands — growing a slot's chain or remapping it to shared
  blocks changes values, never shapes: zero retraces.

``BlockStore`` is the HOST-RAM tier below the pool (ROADMAP item 2,
the Mooncake/SGLang hierarchical-cache shape): when the allocator
reclaims a registered EVICTABLE chain, the chain's block data is
*demoted* — gathered off-pool (``export_chain``, an async device
dispatch staged at eviction time) and materialized into the store by
the engine's between-steps pump — instead of destroyed.  Store entries
are keyed by CONTENT (the nested chunk-key spelling of the full token
prefix, not device block ids), so a chain whose ancestors still live
on-device and a chain demoted whole are both matchable.  At admission
``restore_from_host`` rehydrates the host continuation of a prompt
into freshly allocated, EVICTABLE-registered blocks — one functional
``.at[ids].set`` per pool leaf through the sanctioned ``kv_transfer``
seam — so the ordinary radix match then adopts them: a restore is a
device_put, never a suffix prefill, and tables change values, never
shapes (zero retraces across a demote→restore wave, tested).

Everything here is host-side bookkeeping plus ONE eager masking op;
nothing dispatches a compiled step — that stays the engine's job.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.decode_attention import (init_kv_cache, init_kv_pool,
                                             masked_lengths)

__all__ = ["BlockStore", "KVCacheManager", "PagedKVCacheManager",
           "KVPoolExhausted", "chunk_keys"]


def chunk_keys(tokens, block):
    """Content keys for every FULL ``block``-sized chunk of ``tokens``:
    the nested ``(parent_key, chunk)`` spelling — structurally the whole
    token prefix up to and including each chunk, hashable, with shared
    structure between a chain and its extensions.  Keying the host tier
    by content (instead of device block ids) is what lets a chain whose
    ancestors still live on-device match its demoted continuation."""
    keys, key = [], None
    block = int(block)
    for k in range(len(tokens) // block):
        chunk = tuple(int(t) for t in tokens[k * block:(k + 1) * block])
        key = (key, chunk)
        keys.append(key)
    return keys


def _kv_transfer(leaves):
    """Materialize staged demotion leaves on the host: block on the
    eviction-time device gathers (dispatched long before — device
    program order already ran them ahead of any subsequent pool write)
    and return numpy copies.  This is the device→host half of the tier
    boundary and the tpu-lint-sanctioned transfer seam (PTL017): it is
    called ONLY from ``pump_host_tier`` between scheduler steps, never
    inside a dispatch loop."""
    def fetch(x):
        if isinstance(x, tuple):
            return tuple(fetch(e) for e in x)
        # np.asarray of a jax buffer can alias it read-only — the store
        # owns its bytes (and the corruption seam mutates them), so copy
        return np.array(x)
    return [(fetch(k), fetch(v)) for k, v in leaves]


def kv_transfer(caches, ids, leaves):
    """Scatter host-tier block data back into the pool: one functional
    ``.at[ids].set`` per leaf (a device_put of values into an existing
    buffer — shapes, shardings and programs are untouched, which is the
    zero-retrace argument for restore-on-adopt).  The host→device half
    of the tier boundary and the other sanctioned transfer seam
    (tpu-lint PTL017): called only from ``restore_from_host``, which
    the engine runs at admission — between steps, off the dispatch
    loop."""
    ids = jnp.asarray(np.asarray(ids, np.int32))

    def put(pool, leaf):
        if isinstance(pool, tuple):
            return tuple(put(p, x) for p, x in zip(pool, leaf))
        return pool.at[ids].set(jnp.asarray(leaf).astype(pool.dtype))
    return [(put(kc, lk), put(vc, lv))
            for (kc, vc), (lk, lv) in zip(caches, leaves)]


def _leaf_nbytes(leaf):
    if isinstance(leaf, tuple):
        return sum(_leaf_nbytes(x) for x in leaf)
    return int(leaf.nbytes)


def _leaf_crc(leaf, crc=0):
    if isinstance(leaf, tuple):
        for x in leaf:
            crc = _leaf_crc(x, crc)
        return crc
    return zlib.crc32(np.ascontiguousarray(leaf).tobytes(), crc)


def _leaf_spec_of(leaf):
    if isinstance(leaf, tuple):
        return tuple(_leaf_spec_of(x) for x in leaf)
    return (tuple(leaf.shape), str(leaf.dtype))


class BlockStore:
    """Host-RAM demotion tier for evicted prefix chains.

    A radix map over CONTENT keys (``chunk_keys``) holding one KV
    block's per-layer ``(k, v)`` leaves per entry — ``[C, Hkv, D]``
    data plus ``[C, Hkv]`` scales on int8 pools, numpy, off-pool — under
    its own LRU + byte budget:

    * ``put`` inserts one block's leaves; when the budget overflows, the
      least-recently-used entry AND its registered descendants are
      evicted first (a child is only matchable through its parent, so a
      subtree orphaned by its parent's eviction would be dead weight).
      An entry bigger than the whole budget is rejected.
    * ``fetch`` validates the entry against the pool's expected leaf
      structure AND the CRC recorded at insert; a mismatch (truncated /
      garbled chain — ``FaultPlan(host_tier_corrupt=...)``) drops the
      entry's subtree, counts ``stats["errors"]`` and returns None, so
      wrong bytes are NEVER spliced into a pool — the caller falls back
      to suffix prefill.
    * ``has`` is a pure probe (no LRU touch): routers may ask often.

    Host bookkeeping only; the device halves of demotion/restore live in
    the manager's ``_kv_transfer``/``kv_transfer`` seams.  One store may
    be shared by several managers (engines) as long as their block sizes
    agree — content keys carry the token bytes, so cross-engine hits are
    exactly as safe as same-engine ones.
    """

    def __init__(self, max_bytes, block):
        self.max_bytes = int(max_bytes)
        self.block = int(block)
        if self.max_bytes < 0:
            raise ValueError("BlockStore max_bytes must be >= 0")
        if self.block <= 0:
            raise ValueError("BlockStore block must be > 0")
        self._data = {}     # key -> per-layer [(k, v)] numpy leaves
        self._nbytes = {}   # key -> payload bytes
        self._crc = {}      # key -> crc32 at insert
        self._kids = {}     # parent key -> set(child keys)
        self._lru = {}      # key -> tick
        self._tick = 0
        self.total_bytes = 0
        self.stats = {"demoted": 0, "restored": 0, "evicted": 0,
                      "rejected": 0, "errors": 0}

    @property
    def n_blocks(self):
        return len(self._data)

    def __contains__(self, key):
        return key in self._data

    def has(self, key):
        """Pure presence probe — no LRU touch (probing must not make an
        entry look hot; only a restore-bound ``fetch`` does)."""
        return key in self._data

    @staticmethod
    def key_digest(key):
        """Short stable hex digest of a content key for events/logs (the
        nested key itself spells the whole token prefix)."""
        return format(zlib.crc32(repr(key).encode()), "08x")

    def nbytes_of(self, key):
        return self._nbytes.get(key, 0)

    # ------------------------------------------------------------ mutation
    def _drop_subtree(self, key, stat):
        """Remove ``key`` and every registered descendant; returns the
        dropped keys.  ``stat`` names the stats counter to charge."""
        dropped, stack = [], [key]
        while stack:
            k = stack.pop()
            stack.extend(self._kids.pop(k, ()))
            if k not in self._data:
                continue
            del self._data[k]
            self.total_bytes -= self._nbytes.pop(k)
            self._crc.pop(k, None)
            self._lru.pop(k, None)
            kids = self._kids.get(k[0])
            if kids is not None:
                kids.discard(k)
            dropped.append(k)
            self.stats[stat] += 1
        return dropped

    def put(self, key, leaves):
        """Insert one block's per-layer leaves under content ``key``.
        Returns ``(stored, evicted_keys)``: LRU entries (with subtrees)
        evicted to make room, or ``stored=False`` when the entry alone
        exceeds the budget (counted ``rejected``).  Re-inserting a
        present key refreshes its LRU tick and payload."""
        nb = sum(_leaf_nbytes(k) + _leaf_nbytes(v) for k, v in leaves)
        evicted = []
        if nb > self.max_bytes:
            self.stats["rejected"] += 1
            return False, evicted
        if key in self._data:
            self.total_bytes -= self._nbytes[key]
        while self.total_bytes + nb > self.max_bytes:
            victim = min(self._lru, key=self._lru.get)
            evicted.extend(self._drop_subtree(victim, "evicted"))
        self._data[key] = leaves
        self._nbytes[key] = nb
        self._crc[key] = _leaf_crc(tuple(leaves))
        self._kids.setdefault(key[0], set()).add(key)
        self.total_bytes += nb
        self._tick += 1
        self._lru[key] = self._tick
        self.stats["demoted"] += 1
        return True, evicted

    def fetch(self, key, spec=None):
        """The entry's leaves, validated — or None (absent, or corrupt:
        structure/shape/dtype mismatch against ``spec`` or a CRC
        mismatch; the bad entry's subtree is dropped and ``errors``
        counted, so a broken chain can never splice wrong bytes)."""
        entry = self._data.get(key)
        if entry is None:
            return None
        ok = True
        if spec is not None:
            ok = (len(entry) == len(spec)
                  and all(_leaf_spec_of(k) == sk and _leaf_spec_of(v) == sv
                          for (k, v), (sk, sv) in zip(entry, spec)))
        if ok:
            ok = _leaf_crc(tuple(entry)) == self._crc.get(key)
        if not ok:
            self._drop_subtree(key, "errors")
            return None
        self._tick += 1
        self._lru[key] = self._tick
        self.stats["restored"] += 1
        return entry

    # --------------------------------------------------------- fault seam
    def corrupt(self, key=None, mode="truncate"):
        """Test-only damage seam (``FaultPlan.host_tier_corrupt``):
        truncate (drop the last cached row of every leaf — a structural
        length mismatch ``fetch`` catches against the pool spec) or
        garble (flip payload bytes in place, leaving the insert-time CRC
        stale) the entry at ``key``, or every entry when ``key`` is
        None.  Returns the number of entries damaged."""
        if mode not in ("truncate", "garble"):
            raise ValueError(f"unknown corruption mode {mode!r}")
        keys = [key] if key is not None else list(self._data)
        n = 0
        for k in keys:
            entry = self._data.get(k)
            if entry is None:
                continue
            if mode == "truncate":
                def cut(leaf):
                    if isinstance(leaf, tuple):
                        return tuple(cut(x) for x in leaf)
                    return leaf[:-1]
                self._data[k] = [(cut(kk), cut(vv)) for kk, vv in entry]
            else:
                def garble(leaf):
                    if isinstance(leaf, tuple):
                        return (garble(leaf[0]),) + tuple(leaf[1:])
                    out = np.array(leaf)
                    raw = out.reshape(-1).view(np.uint8)
                    raw[: min(8, raw.size)] ^= 0xFF
                    return out
                kk, vv = entry[0]
                entry[0] = (garble(kk), vv)
            n += 1
        return n

    # ------------------------------------------------------- introspection
    def snapshot(self):
        """JSON-ready occupancy/stats view for debug endpoints."""
        return {"max_bytes": self.max_bytes, "block": self.block,
                "n_blocks": self.n_blocks,
                "total_bytes": self.total_bytes,
                "stats": dict(self.stats)}


def _place_caches(caches, sharding, scale_sharding):
    """Shard-place freshly allocated caches.  A float cache leaf is one
    array; an int8 cache leaf is a ``(data, scale)`` pair whose scale
    array has no trailing ``D`` axis, so it takes its OWN head-sharded
    spec (serving/sharding.kv_scale_pspec) rather than the data spec."""
    def put(leaf):
        if isinstance(leaf, tuple):
            return (jax.device_put(leaf[0], sharding),
                    jax.device_put(leaf[1], scale_sharding
                                   if scale_sharding is not None
                                   else sharding))
        return jax.device_put(leaf, sharding)
    return [(put(k), put(v)) for k, v in caches]


class KVPoolExhausted(RuntimeError):
    """A block allocation could not be satisfied even after evicting
    every refcount-0 cached block.  The engine treats this as
    back-pressure (defer the admission, shed on queue overflow) — never
    a crash mid-stream, because admission reserves a request's worst-case
    block budget up front."""


class KVCacheManager:
    """Slot allocator + KV-cache owner for one fixed-batch engine."""

    def __init__(self, n_layers, batch_size, max_len, num_kv_heads,
                 head_dim, dtype, sharding=None, scale_sharding=None,
                 init_layer=None):
        """``init_layer()`` makes ONE layer's cache leaves (a serving
        family's ``init_layer_cache``: the (k, v) rows pair first, then
        whatever per-slot state the architecture carries beside them);
        ``None`` is the rows pair alone."""
        self.batch_size = int(batch_size)
        self.max_len = int(max_len)
        if init_layer is None:
            init_layer = lambda: init_kv_cache(
                self.batch_size, self.max_len, num_kv_heads, head_dim, dtype)
        caches = [init_layer() for _ in range(n_layers)]
        if sharding is not None:
            caches = _place_caches(caches, sharding, scale_sharding)
        self.caches = caches
        self.sharding = sharding
        # host mirrors of per-slot device state
        self.lengths = np.zeros((self.batch_size,), np.int32)
        self.reqs = [None] * self.batch_size

    # ------------------------------------------------------------- slots
    def free_slots(self):
        """Free slot indices, lowest first (the admission fill order)."""
        return [i for i in range(self.batch_size) if self.reqs[i] is None]

    def occupied(self):
        """Count of slots holding a live request."""
        return sum(r is not None for r in self.reqs)

    def any_live(self):
        return any(r is not None for r in self.reqs)

    def live_tokens(self):
        """Total context tokens held by live slots (capacity-utilisation
        numerator: dense strands ``B*Lmax - live_tokens`` cache rows)."""
        return int(sum(int(self.lengths[i])
                       for i in range(self.batch_size)
                       if self.reqs[i] is not None))

    def assign(self, slot, request):
        """Bind ``request`` to ``slot`` (admission).  Assigning over a
        live slot raises: the old occupant's cache rows would be silently
        orphaned and its retirement would then double-free the slot."""
        if self.reqs[slot] is not None:
            raise ValueError(
                f"slot {slot} already holds request "
                f"{getattr(self.reqs[slot], 'rid', None)!r} — release it "
                "before assigning (double-assign orphans the occupant)")
        self.reqs[slot] = request

    def release(self, slot):
        """Free ``slot`` (retirement).  The cache rows are NOT touched:
        ``device_lengths`` parks the slot at ``max_len`` so subsequent
        writes drop, and the next occupant's prefill overwrites them.
        Releasing a free slot raises: a silent double-free lets two
        admissions claim the same slot from ``free_slots``."""
        if self.reqs[slot] is None:
            raise ValueError(
                f"slot {slot} is already free — double-release corrupts "
                "the slot free list")
        self.reqs[slot] = None

    # ------------------------------------------------------------ device
    def device_lengths(self, active):
        """The device lengths operand for one dispatch: the host mirror
        with every non-``active`` slot masked to ``max_len`` (write-drop
        parking)."""
        # a host-side COPY: the CPU backend may alias a numpy buffer
        # zero-copy, dispatch is asynchronous, and the scheduler bumps
        # ``lengths`` in place right after dispatching — an aliased
        # operand would race with the program reading it
        return masked_lengths(jnp.asarray(self.lengths.copy()),
                              jnp.asarray(active), self.max_len)


class PagedKVCacheManager(KVCacheManager):
    """Block allocator + radix prefix cache over a paged KV pool.

    Same slot interface as the dense manager (the engine's scheduler is
    geometry-blind) plus the block machinery:

    * ``caches`` — per-layer ``(k, v)`` POOL pairs ``[N, C, Hkv, D]``
      where ``N = max_live_tokens // C``.  Concurrency is budgeted in
      TOKENS, not slots: the engine may run far more slots than
      ``N*C / Lmax`` dense equivalents as long as live contexts fit.
    * ``block_tables`` — host int32 ``[B, W]`` (``W = Lmax / C``) mirror
      of each slot's logical-chunk -> physical-block chain; unmapped
      entries hold the sentinel ``N`` so device writes there DROP (the
      paged continuation of the write-drop parking invariant).
    * refcounts / radix map / LRU — see the module docstring.

    Every block is in exactly one of three states: on the free list,
    LIVE (refcount > 0), or EVICTABLE (refcount 0 but still registered
    as a cached prefix, tracked LRU).  ``refcnt[child] <= refcnt[parent]``
    holds along every registered chain because prefixes are adopted and
    released whole — which is what makes subtree eviction safe.
    """

    def __init__(self, n_layers, batch_size, max_len, num_kv_heads,
                 head_dim, dtype, block, max_live_tokens, sharding=None,
                 on_event=None, scale_sharding=None, host_store=None):
        self.batch_size = int(batch_size)
        self.max_len = int(max_len)
        self.block = int(block)
        if self.block <= 0 or self.max_len % self.block:
            raise ValueError(
                f"kv block ({block}) must divide max_len ({max_len}): the "
                "paged read is the chunked loop and a partial tail block "
                "would break the clamped-tail masking")
        self.width = self.max_len // self.block
        self.num_blocks = int(max_live_tokens) // self.block
        if self.num_blocks < self.width:
            raise ValueError(
                f"max_live_tokens ({max_live_tokens}) must cover at least "
                f"one full-length request ({max_len} tokens): a smaller "
                "pool could never admit a valid submit() and would defer "
                "it forever")
        caches = [init_kv_pool(self.num_blocks, self.block, num_kv_heads,
                               head_dim, dtype) for _ in range(n_layers)]
        if sharding is not None:
            caches = _place_caches(caches, sharding, scale_sharding)
        self.caches = caches
        self.sharding = sharding
        self.lengths = np.zeros((self.batch_size,), np.int32)
        self.reqs = [None] * self.batch_size
        # ---- block state (host-side; sentinel num_blocks = unmapped)
        self.block_tables = np.full((self.batch_size, self.width),
                                    self.num_blocks, np.int32)
        self.refcnt = np.zeros((self.num_blocks,), np.int32)
        self._free = list(range(self.num_blocks - 1, -1, -1))  # pop() -> 0
        self._mapped = [0] * self.batch_size       # chunks mapped per slot
        self._resv_left = [0] * self.batch_size    # reserved, unallocated
        # ---- draft tenancy: a SECOND chain per slot for the resident
        # draft model's KV.  Blocks are model-agnostic bytes, so draft
        # chains draw from the same free list / refcounts / allocator —
        # the manager only keeps the chains (and the radix namespace,
        # below) apart.  Draft blocks are freed OUTRIGHT at refcount 0
        # (never LRU-parked, never host-demoted): draft KV is the small
        # model's — cheap to recompute — and parking it would displace
        # target prefixes from the LRU and the host tier.
        self.draft_tables = np.full((self.batch_size, self.width),
                                    self.num_blocks, np.int32)
        self._dmapped = [0] * self.batch_size      # draft chunks per slot
        self._draft_blocks = set()                 # live draft block ids
        # ---- radix prefix map (root parent id = -1)
        self._node = {}     # (parent_block, chunk tokens) -> block id
        self._key_of = {}   # registered block id -> its key
        self._kids = {}     # parent block id -> set(registered child ids)
        self._lru = {}      # evictable block id -> release tick
        self._tick = 0
        self._on_event = on_event
        # ---- host tier (BlockStore): eviction demotes instead of
        # destroying; staged (keys, device leaves) pairs wait here for
        # the engine's between-steps pump to materialize them
        if host_store is not None and host_store.block != self.block:
            raise ValueError(
                f"host tier block size ({host_store.block}) must match "
                f"the pool block size ({self.block}): content keys are "
                "chunked at the block width")
        self._host = host_store
        self._pending_demote = []

    def _emit(self, kind, **info):
        if self._on_event is not None:
            self._on_event(kind, **info)

    def _check_block(self, b):
        if not 0 <= b < self.num_blocks:
            raise ValueError(
                f"block index {b} out of range [0, {self.num_blocks})")

    # ---------------------------------------------------------- accounting
    def free_count(self):
        return len(self._free)

    def evictable_count(self):
        return len(self._lru)

    def blocks_used(self):
        """Blocks that are live OR holding an evictable cached prefix."""
        return self.num_blocks - len(self._free)

    def draft_blocks_used(self):
        """LIVE draft-chain blocks.  Draft blocks are freed outright at
        refcount 0 (see ``__init__``), so this returns to 0 once every
        spec request drains — the ``serving_kv_blocks_used{model=draft}``
        accounting invariant."""
        return len(self._draft_blocks)

    def outstanding(self):
        """Blocks promised to admitted slots but not yet allocated."""
        return sum(self._resv_left)

    def can_reserve(self, n_blocks):
        """Whether ``n_blocks`` NEW allocations can be promised without
        starving any slot's existing reservation.  Evictable blocks count
        as available — the allocator reclaims them on demand."""
        return n_blocks <= (len(self._free) + len(self._lru)
                            - self.outstanding())

    def reserve(self, slot, n_blocks):
        """Record ``slot``'s remaining worst-case block budget (admission
        time, after shared prefix chunks are subtracted).  ``ensure_rows``
        draws it down; ``release`` clears it."""
        self._resv_left[slot] = int(n_blocks)

    # ---------------------------------------------------------- allocator
    def _content_key(self, b):
        """The host-tier content key of registered block ``b``: its chunk
        path from the radix root, spelled as ``chunk_keys`` nests it."""
        parts = []
        while b != -1:
            parent, chunk = self._key_of[b]
            parts.append(chunk)
            b = parent
        key = None
        for chunk in reversed(parts):
            key = (key, chunk)
        return key

    def _evict_subtree(self, root):
        """Reclaim evictable ``root`` and every registered descendant
        (all refcount-0 by the chain invariant) back to the free list.

        With a host tier attached this is DEMOTION, not destruction: the
        subtree's block data is gathered off-pool here (``export_chain``
        — an async device dispatch; program order runs it before any
        later write to the freed blocks) and staged with its content
        keys for ``pump_host_tier`` to materialize between steps.
        Nothing blocks on the step path."""
        parent = self._key_of[root][0]
        self._kids.get(parent, set()).discard(root)
        demote = self._host is not None
        stack = [(root, self._content_key(root) if demote else None)]
        n, order, keys = 0, [], []
        while stack:
            b, ck = stack.pop()
            if self.refcnt[b] != 0:
                raise RuntimeError(
                    f"prefix chain invariant broken: evicting block {b} "
                    f"with refcount {int(self.refcnt[b])}")
            for kid in self._kids.pop(b, ()):
                stack.append(
                    (kid, (ck, self._key_of[kid][1]) if demote else None))
            if demote and not self._host.has(ck):
                order.append(b)
                keys.append(ck)
            self._node.pop(self._key_of.pop(b), None)
            self._lru.pop(b, None)
            self._free.append(b)
            n += 1
            self._emit("block_free", block=int(b), evicted=True)
        if order:
            self._pending_demote.append((keys, self.export_chain(order)))
        return n

    def alloc_block(self):
        """One free block (refcount 1), evicting the least-recently-
        released cached prefix subtree if the free list is dry.  Raises
        ``KVPoolExhausted`` when every block is live."""
        if not self._free:
            if not self._lru:
                raise KVPoolExhausted(
                    f"kv pool exhausted: all {self.num_blocks} blocks of "
                    f"{self.block} tokens are live")
            self._evict_subtree(min(self._lru, key=self._lru.get))
        b = self._free.pop()
        self.refcnt[b] = 1
        self._emit("block_alloc", block=int(b))
        return b

    def free_block(self, b):
        """Drop one reference.  At refcount 0 a registered block parks as
        EVICTABLE (its cached prefix stays matchable); an unregistered one
        returns to the free list.  Underflow and OOB raise — a silent
        double-free would let two slots claim the same physical block."""
        b = int(b)
        self._check_block(b)
        if self.refcnt[b] <= 0:
            raise ValueError(
                f"refcount underflow: block {b} is already free "
                "(double-free corrupts the pool)")
        self.refcnt[b] -= 1
        if self.refcnt[b] == 0:
            if b in self._draft_blocks:
                # draft policy: unregister from the draft radix namespace
                # and free outright — never LRU-park, never demote
                key = self._key_of.pop(b, None)
                if key is not None:
                    self._node.pop(key, None)
                    self._kids.get(key[0], set()).discard(b)
                    self._kids.pop(b, None)
                self._draft_blocks.discard(b)
                self._free.append(b)
            elif b in self._key_of:
                self._tick += 1
                self._lru[b] = self._tick
            else:
                self._free.append(b)
            self._emit("block_free", block=b, evicted=False)

    def ensure_rows(self, slot, upto):
        """Grow ``slot``'s chain to cover logical rows ``[0, upto)``
        (called before every dispatch that may write those rows).  Rows
        past ``max_len`` are silently capped — the device drops those
        writes anyway (parking invariant)."""
        need = min(-(-int(upto) // self.block), self.width)
        while self._mapped[slot] < need:
            b = self.alloc_block()
            self.block_tables[slot, self._mapped[slot]] = b
            self._mapped[slot] += 1
            if self._resv_left[slot] > 0:
                self._resv_left[slot] -= 1
        return self._mapped[slot]

    def ensure_draft_rows(self, slot, upto):
        """Grow ``slot``'s DRAFT chain to cover logical rows
        ``[0, upto)`` — the draft-model twin of ``ensure_rows``, drawing
        the same free list and the same admission reservation (a spec
        engine reserves both chains' worst case up front)."""
        need = min(-(-int(upto) // self.block), self.width)
        while self._dmapped[slot] < need:
            b = self.alloc_block()
            self._draft_blocks.add(b)
            self.draft_tables[slot, self._dmapped[slot]] = b
            self._dmapped[slot] += 1
            if self._resv_left[slot] > 0:
                self._resv_left[slot] -= 1
        return self._dmapped[slot]

    # ------------------------------------------------------- prefix reuse
    def match_prefix(self, tokens, touch=True):
        """Longest cached prefix of ``tokens`` -> (matched_tokens, blocks).

        Only FULL blocks are shareable, and the match is capped at
        ``((p-1)//C)*C`` so at least one suffix token always prefills —
        the suffix forward is what produces the first-token logits.

        A match is a HIT: with ``touch`` (the admission default) every
        matched block still parked EVICTABLE gets a fresh LRU tick, so a
        hot shared prefix cannot be reclaimed ahead of a cold one just
        because nobody released it recently (before this fix only
        ``release`` moved the LRU clock).  Pure probes — a router asking
        every replica, ``prefix_lookup`` — pass ``touch=False`` so
        asking does not fake heat."""
        cap = max(0, (len(tokens) - 1) // self.block)
        parent, out = -1, []
        for k in range(cap):
            chunk = tuple(int(t) for t in
                          tokens[k * self.block:(k + 1) * self.block])
            b = self._node.get((parent, chunk))
            if b is None:
                break
            if touch and b in self._lru:
                self._tick += 1
                self._lru[b] = self._tick
            out.append(b)
            parent = b
        return len(out) * self.block, out

    def adopt_prefix(self, slot, blocks):
        """Map shared prefix ``blocks`` at the head of fresh ``slot``'s
        chain (admission after a radix hit): refcounts bump and evictable
        blocks return to LIVE.  Decode never writes below the adopted
        span, so no copy-on-write is needed."""
        if self._mapped[slot]:
            raise ValueError(
                f"adopt_prefix: slot {slot} already maps "
                f"{self._mapped[slot]} blocks")
        for w, b in enumerate(blocks):
            b = int(b)
            self._check_block(b)
            self.refcnt[b] += 1
            if self.refcnt[b] == 1:
                self._lru.pop(b, None)
            self.block_tables[slot, w] = b
        self._mapped[slot] = len(blocks)

    def register_prefix(self, slot, tokens):
        """Publish ``slot``'s full-block prefix chain into the radix map.

        Called at FIRST-TOKEN EMISSION (after the prefill's finite check
        passed), never at dispatch — registering earlier could publish
        NaN-poisoned blocks that a later hit would silently adopt.  First
        writer wins per chunk key; on a collision (two identical prompts
        prefilled concurrently) the rest of our chain stays private —
        mixing blocks across chains would break the refcount ordering
        that makes subtree eviction safe."""
        parent = -1
        n_full = min(len(tokens) // self.block, self._mapped[slot])
        for k in range(n_full):
            chunk = tuple(int(t) for t in
                          tokens[k * self.block:(k + 1) * self.block])
            key = (parent, chunk)
            b = int(self.block_tables[slot, k])
            cur = self._node.get(key)
            if cur is None:
                self._node[key] = b
                self._key_of[b] = key
                self._kids.setdefault(parent, set()).add(b)
                parent = b
            elif cur == b:          # adopted shared block: walk through
                parent = b
            else:                   # lost the race: keep the rest private
                break

    # ------------------------------------------------ draft radix namespace
    # The draft model's prefix chains live in the SAME radix structures
    # (_node/_key_of/_kids) under a salted root chunk, so two concurrent
    # requests with an identical prompt share one draft prefix chain the
    # same way they share the target's — while a draft chunk can never
    # collide with (or be adopted as) a target chunk, and its host-tier
    # content key is salted by construction.  Unlike target chunks, draft
    # chunks are only shareable while some slot still references them:
    # refcount 0 frees a draft block outright (see ``free_block``).

    _DRAFT_SALT = "__draft__"

    def _draft_chunk(self, tokens, k):
        chunk = tuple(int(t) for t in
                      tokens[k * self.block:(k + 1) * self.block])
        return ((self._DRAFT_SALT,) + chunk) if k == 0 else chunk

    def match_draft_prefix(self, tokens, touch=True):
        """Longest LIVE draft-namespace prefix of ``tokens`` ->
        (matched_tokens, blocks) — ``match_prefix`` over the salted
        namespace (``touch`` kept for interface symmetry; draft blocks
        never sit in the LRU, so there is no heat to fake)."""
        cap = max(0, (len(tokens) - 1) // self.block)
        parent, out = -1, []
        for k in range(cap):
            b = self._node.get((parent, self._draft_chunk(tokens, k)))
            if b is None:
                break
            out.append(b)
            parent = b
        return len(out) * self.block, out

    def register_draft_prefix(self, slot, tokens):
        """Publish ``slot``'s full-block DRAFT chain into the salted
        namespace — ``register_prefix``'s first-writer-wins walk over
        ``draft_tables``."""
        parent = -1
        n_full = min(len(tokens) // self.block, self._dmapped[slot])
        for k in range(n_full):
            key = (parent, self._draft_chunk(tokens, k))
            b = int(self.draft_tables[slot, k])
            cur = self._node.get(key)
            if cur is None:
                self._node[key] = b
                self._key_of[b] = key
                self._kids.setdefault(parent, set()).add(b)
                parent = b
            elif cur == b:
                parent = b
            else:
                break

    def adopt_draft_prefix(self, slot, blocks):
        """Map shared draft ``blocks`` at the head of ``slot``'s fresh
        draft chain (admission after a ``match_draft_prefix`` hit) —
        refcounts bump exactly like ``adopt_prefix``."""
        if self._dmapped[slot]:
            raise ValueError(
                f"adopt_draft_prefix: slot {slot} already maps "
                f"{self._dmapped[slot]} draft blocks")
        for w, b in enumerate(blocks):
            b = int(b)
            self._check_block(b)
            self.refcnt[b] += 1
            self.draft_tables[slot, w] = b
        self._dmapped[slot] = len(blocks)

    # ---------------------------------------------------------- host tier
    @property
    def host_tier(self):
        """The attached ``BlockStore`` demotion target (None = eviction
        destroys, the pre-tier behavior)."""
        return self._host

    def _block_spec(self):
        """Expected per-block leaf structure for host-tier validation:
        per-layer ``(k, v)`` of ``(shape, dtype)`` descriptors over ONE
        block's rows (tuple-nested on int8 pools)."""
        def spec(leaf):
            if isinstance(leaf, tuple):
                return tuple(spec(x) for x in leaf)
            return (tuple(leaf.shape[1:]), str(leaf.dtype))
        return [(spec(k), spec(v)) for k, v in self.caches]

    def host_match(self, tokens, matched_tokens):
        """Host-tier tokens CONTINUING a device match of
        ``matched_tokens``: contiguous chunks present in the store from
        the device break onward, capped like ``match_prefix`` so at
        least one suffix token always prefills.  Pure probe — no store
        LRU touch."""
        if self._host is None:
            return 0
        cap = max(0, (len(tokens) - 1) // self.block)
        k0 = int(matched_tokens) // self.block
        n = 0
        for k, key in enumerate(chunk_keys(tokens[:cap * self.block],
                                           self.block)):
            if k < k0:
                continue
            if not self._host.has(key):
                break
            n += 1
        return n * self.block

    def restore_from_host(self, tokens, rid=None, min_blocks=1):
        """Rehydrate the host-tier continuation of ``tokens`` into
        freshly allocated, EVICTABLE-registered blocks; returns blocks
        restored.  The caller (admission) simply re-runs
        ``match_prefix`` afterwards and adopts through the ordinary
        radix path — restored blocks enter the exact state a released
        registered chain parks in (refcount 0, fresh LRU tick), so no
        new invariants exist.

        Chains shorter than ``min_blocks`` are left to suffix prefill
        (the restore-vs-reprefill crossover knob).  Validation failures
        (a corrupted store entry) stop the walk at the bad chunk, emit
        ``host_error`` and leave earlier restored blocks in place —
        wrong bytes are never spliced.  Allocation stops rather than
        evict any block of the chain being extended (or just restored):
        a restore must not cannibalize its own prefix."""
        if self._host is None:
            return 0
        cap = max(0, (len(tokens) - 1) // self.block)
        keys = chunk_keys(tokens[:cap * self.block], self.block)
        # device walk: the chain restore continues, protected from the
        # allocator below (match_prefix's touch already refreshed these
        # at admission, but a tiny pool can still reach them)
        parent, k0, protected = -1, 0, set()
        for k, key in enumerate(keys):
            b = self._node.get((parent, key[1]))
            if b is None:
                break
            parent = b
            protected.add(b)
            k0 = k + 1
        spec = self._block_spec()
        entries, errors = [], 0
        for k in range(k0, cap):
            if not self._host.has(keys[k]):
                break
            leaves = self._host.fetch(keys[k], spec)
            if leaves is None:
                errors += 1
                self._emit("host_error", rid=rid,
                           key=BlockStore.key_digest(keys[k]))
                break
            entries.append((keys[k][1], leaves))
        if not errors and len(entries) < max(1, int(min_blocks)):
            return 0
        if not entries:
            return 0
        blocks = []
        for _ in entries:
            if not self._free:
                if not self._lru:
                    break
                if min(self._lru, key=self._lru.get) in protected:
                    break
            blocks.append(self.alloc_block())
            protected.add(blocks[-1])
        entries = entries[:len(blocks)]
        if not blocks:
            return 0
        # one functional scatter per pool leaf for the whole restored run
        def stack(li, which):
            parts = [e[1][li][which] for e in entries]
            if isinstance(parts[0], tuple):
                return tuple(np.stack([p[j] for p in parts])
                             for j in range(len(parts[0])))
            return np.stack(parts)
        stacked = [(stack(li, 0), stack(li, 1))
                   for li in range(len(self.caches))]
        self.caches = kv_transfer(self.caches, blocks, stacked)
        nbytes = 0
        for b, (chunk, leaves) in zip(blocks, entries):
            key = (parent, chunk)
            self._node[key] = b
            self._key_of[b] = key
            self._kids.setdefault(parent, set()).add(b)
            self.refcnt[b] = 0
            self._tick += 1
            self._lru[b] = self._tick
            parent = b
            nbytes += sum(_leaf_nbytes(kk) + _leaf_nbytes(vv)
                          for kk, vv in leaves)
        self._emit("restore", rid=rid, n_blocks=len(blocks), bytes=nbytes,
                   key=BlockStore.key_digest(self._content_key(blocks[0])))
        return len(blocks)

    def pump_host_tier(self):
        """Materialize every staged demotion into the host store — the
        engine calls this BETWEEN scheduler steps (never inside the
        dispatch loop; the ``_kv_transfer`` block lands here, where the
        eviction-time gathers finished long ago).  Returns blocks
        demoted."""
        if self._host is None or not self._pending_demote:
            return 0
        staged, self._pending_demote = self._pending_demote, []
        demoted = 0
        for keys, leaves in staged:
            host = _kv_transfer(leaves)

            def cut(leaf, i):
                if isinstance(leaf, tuple):
                    return tuple(cut(x, i) for x in leaf)
                return np.ascontiguousarray(leaf[i])
            stored_n, stored_bytes = 0, 0
            for i, key in enumerate(keys):
                per_block = [(cut(kk, i), cut(vv, i)) for kk, vv in host]
                stored, evicted = self._host.put(key, per_block)
                for ek in evicted:
                    self._emit("host_evict",
                               key=BlockStore.key_digest(ek))
                if stored:
                    stored_n += 1
                    stored_bytes += self._host.nbytes_of(key)
            if stored_n:
                demoted += stored_n
                self._emit("demote", n_blocks=stored_n,
                           bytes=stored_bytes,
                           key=BlockStore.key_digest(keys[0]))
        return demoted

    def corrupt_host(self, tokens=None, mode="truncate"):
        """Damage the host-tier entries along ``tokens``'s chunk chain
        (or every entry when None) — the manager half of the
        ``FaultPlan(host_tier_corrupt=...)`` seam.  Returns entries
        damaged."""
        if self._host is None:
            return 0
        if tokens is None:
            return self._host.corrupt(None, mode=mode)
        n = 0
        for key in chunk_keys(tokens, self.block):
            if self._host.has(key):
                n += self._host.corrupt(key, mode=mode)
        return n

    # ------------------------------------------------- block-chain transfer
    # The prefill/decode split (serving/disagg.py) ships a finished
    # request's KV as its BLOCK CHAIN: export gathers the chain's rows out
    # of every pool leaf ([n, C, Hkv, D] data + [n, C, Hkv] int8 scales —
    # the head axis stays at index 2, so the TP pool pspec applies to the
    # transfer leaves unchanged), import scatters them into freshly
    # allocated blocks of ANOTHER pool, and splice maps those blocks under
    # a fresh slot's table row.  Block-table indirection is what makes the
    # handoff shape-free: the decode programs see new table VALUES, never
    # new shapes, so a migrated request decodes with zero retraces.

    def block_chain(self, rid):
        """The physical block ids backing request ``rid``'s mapped chain,
        logical order.  Public accessor for export / accounting tests —
        disagg code never walks ``block_tables``/``_mapped`` directly."""
        for slot, r in enumerate(self.reqs):
            if r is not None and r.rid == rid:
                return [int(self.block_tables[slot, w])
                        for w in range(self._mapped[slot])]
        raise KeyError(f"no resident request with rid {rid!r}")

    def export_chain(self, blocks):
        """Gather chain ``blocks``'s rows out of every pool leaf ->
        per-layer ``(k, v)`` transfer leaves (``[n, C, Hkv, D]`` data,
        plus ``[n, C, Hkv]`` scales on int8 pools).  An eager device
        gather: the copies are materialized in device program order, so
        the source blocks may be released (and even rewritten by later
        dispatches) immediately after this returns."""
        for b in blocks:
            self._check_block(int(b))
        ids = jnp.asarray(np.asarray(blocks, np.int32))

        def take(leaf):
            if isinstance(leaf, tuple):
                return (leaf[0][ids], leaf[1][ids])
            return leaf[ids]
        return [(take(k), take(v)) for k, v in self.caches]

    def import_chain(self, leaves):
        """Scatter transfer ``leaves`` (``export_chain``'s output, one
        ``(k, v)`` per layer) into freshly allocated blocks of THIS pool;
        returns the new block ids (each refcount 1, owned by the caller
        until spliced or freed).  All-or-nothing: if the pool cannot
        cover the whole chain, every partially allocated block is
        returned to the free list and ``KVPoolExhausted`` propagates —
        the migration abort path leaks nothing."""
        if len(leaves) != len(self.caches):
            raise ValueError(
                f"import_chain: {len(leaves)} layers of transfer leaves "
                f"for a {len(self.caches)}-layer pool")
        if isinstance(leaves[0][0], tuple) != isinstance(
                self.caches[0][0], tuple):
            raise ValueError(
                "import_chain: transfer-leaf structure does not match "
                "this pool's KV quantization (int8 pools carry "
                "(data, scale) leaf pairs) — source and destination "
                "engines must use the same kv_dtype")
        k0 = leaves[0][0]
        n = (k0[0] if isinstance(k0, tuple) else k0).shape[0]
        blocks = []
        try:
            for _ in range(n):
                blocks.append(self.alloc_block())
        except KVPoolExhausted:
            for b in blocks:
                self.free_block(b)
            raise
        ids = jnp.asarray(np.asarray(blocks, np.int32))

        def put(pool, leaf):
            if isinstance(pool, tuple):
                return (pool[0].at[ids].set(leaf[0].astype(pool[0].dtype)),
                        pool[1].at[ids].set(leaf[1].astype(pool[1].dtype)))
            return pool.at[ids].set(leaf.astype(pool.dtype))
        self.caches = [(put(kc, lk), put(vc, lv))
                       for (kc, vc), (lk, lv) in zip(self.caches, leaves)]
        return blocks

    def splice_chain(self, slot, blocks):
        """Map imported ``blocks`` at the head of fresh ``slot``'s chain
        (the decode-side half of a migration).  Unlike ``adopt_prefix``
        the blocks are already OWNED (refcount 1 from ``import_chain``),
        so ownership transfers instead of bumping — a block someone else
        still references cannot be spliced."""
        if self._mapped[slot]:
            raise ValueError(
                f"splice_chain: slot {slot} already maps "
                f"{self._mapped[slot]} blocks")
        for b in blocks:
            b = int(b)
            self._check_block(b)
            if self.refcnt[b] != 1:
                raise ValueError(
                    f"splice_chain: block {b} has refcount "
                    f"{int(self.refcnt[b])}, expected exclusive ownership "
                    "(1) from import_chain")
        for w, b in enumerate(blocks):
            self.block_tables[slot, w] = int(b)
        self._mapped[slot] = len(blocks)

    # -------------------------------------------------------------- slots
    def release(self, slot):
        """Retire ``slot``: unreference its whole chain (shared prefix
        blocks may stay EVICTABLE for the next identical prompt), reset
        the table row to the sentinel, clear the reservation.  The draft
        chain is unreferenced LEAF-FIRST so a shared draft parent stays
        registered until its registered children are gone (draft blocks
        free outright at refcount 0, unregistering as they go)."""
        super().release(slot)
        for w in range(self._mapped[slot]):
            self.free_block(int(self.block_tables[slot, w]))
        self.block_tables[slot, :] = self.num_blocks
        self._mapped[slot] = 0
        for w in range(self._dmapped[slot] - 1, -1, -1):
            self.free_block(int(self.draft_tables[slot, w]))
        self.draft_tables[slot, :] = self.num_blocks
        self._dmapped[slot] = 0
        self._resv_left[slot] = 0

    # -------------------------------------------------------------- device
    def device_tables(self):
        """The traced ``[B, W]`` block-table operand for one dispatch — a
        COPY of the host mirror (see ``device_lengths``: the mirror is
        mutated in place while dispatches are in flight)."""
        return jnp.asarray(self.block_tables.copy())

    def device_draft_tables(self):
        """The traced ``[B, W]`` DRAFT block-table operand — same pool,
        second tenant (a copy, like ``device_tables``)."""
        return jnp.asarray(self.draft_tables.copy())
