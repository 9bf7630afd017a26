"""Paged KV cache with block tables and prefix reuse (ROADMAP item 2).

The acceptance properties on the CPU mesh at f32:

* the paged engine's token streams are BYTE-IDENTICAL to the dense
  engine on the same workload, across greedy/spec, including
  shared-prefix prompts that exercise radix hits and block adoption
  mid-run;
* token-budget admission DEFERS (and later completes) requests the pool
  cannot cover — exhaustion is back-pressure, never a crash;
* a warm paged engine runs a staggered workload with prefix hits,
  evictions, and mid-stream chain growth at ZERO retraces (the table is
  a traced operand: values change, shapes never do);
* the block allocator's edge cases (double-free, OOB, refcount
  underflow, adopt-over-mapped, pool exhaustion) raise typed errors.

The fast B3 smoke and allocator units are tier-1; the full parity
matrix with mixed block/chunk geometries is ``slow``.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import assert_no_retrace
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import MetricsRegistry
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.serving.kv_cache import KVPoolExhausted, PagedKVCacheManager


def _tiny_model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(dtype="float32")
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


def _run(model, prompts, new_lens, **kw):
    eng = ServingEngine(model, **kw)
    for p, n in zip(prompts, new_lens):
        eng.submit(Request(p, int(n)))
    done = eng.run()
    assert not eng.has_work
    return {r.rid: list(r.output_ids) for r in done}, eng


def _shared_prefix_prompts(rng, sizes, share=(2, 4)):
    """Random prompts where every index in ``share[1:]`` reuses the
    first 20 tokens of prompt ``share[0]`` — the radix-hit workload."""
    prompts = [rng.integers(1, 200, size=n).tolist() for n in sizes]
    head = prompts[share[0]][:20]
    for i in share[1:]:
        prompts[i] = head + rng.integers(1, 200, size=len(prompts[i]) - 20
                                         ).tolist()
    return prompts


PAGED = dict(kv_block=16, max_live_tokens=3 * 128)
GEOM = dict(batch_size=3, max_len=128, decode_chunk=16, prefill_chunk=16,
            instrument=False, recorder=False)


# ---------------------------------------------------------------------------
# allocator units (pure host — no engine, no device programs)
# ---------------------------------------------------------------------------

def _mgr(**kw):
    d = dict(n_layers=1, batch_size=2, max_len=32, num_kv_heads=1,
             head_dim=4, dtype="float32", block=8, max_live_tokens=64)
    d.update(kw)
    return PagedKVCacheManager(**d)


class TestPagedAllocator:
    def test_geometry_validation(self):
        with pytest.raises(ValueError, match="must divide max_len"):
            _mgr(block=12)
        with pytest.raises(ValueError, match="at least"):
            _mgr(max_live_tokens=24)  # 3 blocks < width 4

    def test_double_free_raises(self):
        m = _mgr()
        b = m.alloc_block()
        m.free_block(b)
        with pytest.raises(ValueError, match="refcount underflow"):
            m.free_block(b)

    def test_oob_block_raises(self):
        m = _mgr()
        with pytest.raises(ValueError, match="out of range"):
            m.free_block(m.num_blocks)
        with pytest.raises(ValueError, match="out of range"):
            m.free_block(-1)

    def test_exhaustion_is_typed_and_recoverable(self):
        m = _mgr()  # 8 blocks
        held = [m.alloc_block() for _ in range(m.num_blocks)]
        with pytest.raises(KVPoolExhausted, match="exhausted"):
            m.alloc_block()
        m.free_block(held[0])  # unregistered -> straight to the free list
        assert m.alloc_block() == held[0]

    def test_adopt_over_mapped_slot_raises(self):
        m = _mgr()
        m.assign(0, object())
        m.ensure_rows(0, 8)
        with pytest.raises(ValueError, match="already maps"):
            m.adopt_prefix(0, [m.alloc_block()])

    def test_release_parks_registered_blocks_evictable(self):
        m = _mgr()
        toks = list(range(100, 120))  # 20 tokens -> 2 full blocks of 8
        m.assign(0, object())
        m.ensure_rows(0, len(toks))
        m.register_prefix(0, toks)
        m.release(0)
        # 2 registered blocks park evictable; the unregistered tail block
        # (20 tokens map 3 blocks, only 2 are full) returns to the free
        # list straight away
        assert m.evictable_count() == 2 and m.free_count() == 6
        # the cached chain stays matchable, capped below the last token
        got, blocks = m.match_prefix(toks)
        assert got == 16 and len(blocks) == 2
        # ...and a full re-adoption revives it without fresh allocations
        m.assign(0, object())
        m.adopt_prefix(0, blocks)
        assert m.evictable_count() == 0 and m.free_count() == 6

    def test_eviction_reclaims_lru_subtree(self):
        m = _mgr()
        for slot, base in ((0, 100), (1, 300)):
            toks = list(range(base, base + 17))
            m.assign(slot, object())
            m.ensure_rows(slot, len(toks))
            m.register_prefix(slot, toks)
            m.release(slot)  # slot 0's chain released first -> older LRU
        # per slot: 2 registered blocks evictable + 1 unregistered tail
        # block (17 tokens map 3) straight back to the free list
        assert m.free_count() == 4 and m.evictable_count() == 4
        held = [m.alloc_block() for _ in range(5)]  # 4 free + 1st eviction
        assert len(held) == 5
        # slot 0's subtree (released first) was reclaimed; slot 1's stays
        assert m.match_prefix(list(range(100, 117)))[0] == 0
        assert m.match_prefix(list(range(300, 317)))[0] == 16
        assert m.free_count() == 1 and m.evictable_count() == 2

    def test_can_reserve_counts_outstanding_promises(self):
        m = _mgr()  # 8 free, 0 evictable
        assert m.can_reserve(8) and not m.can_reserve(9)
        m.assign(0, object())
        m.reserve(0, 5)
        assert m.outstanding() == 5
        assert m.can_reserve(3) and not m.can_reserve(4)
        m.ensure_rows(0, 16)  # draws 2 blocks off the reservation
        assert m.outstanding() == 3
        assert m.can_reserve(3) and not m.can_reserve(4)

    def test_register_collision_keeps_rest_private(self):
        m = _mgr()
        toks = list(range(100, 117))
        for slot in (0, 1):
            m.assign(slot, object())
            m.ensure_rows(slot, len(toks))
        m.register_prefix(0, toks)
        m.register_prefix(1, toks)  # loses the race: chain stays private
        got, blocks = m.match_prefix(toks)
        assert blocks == [int(m.block_tables[0, w]) for w in range(2)]


# ---------------------------------------------------------------------------
# engine integration (tier-1)
# ---------------------------------------------------------------------------

class TestPagedEngineSmoke:
    def test_constructor_validation(self):
        model = _tiny_model()
        with pytest.raises(ValueError, match="requires kv_block"):
            ServingEngine(model, batch_size=2, max_len=64,
                          prefill_chunk=16, max_live_tokens=128)
        with pytest.raises(ValueError):
            ServingEngine(model, batch_size=2, max_len=64,
                          prefill_chunk=16, kv_block=12)

    def test_paged_matches_dense_all_modes(self):
        rng = np.random.default_rng(3)
        prompts = _shared_prefix_prompts(rng, (7, 19, 33, 12, 25),
                                         share=(2, 4))
        new_lens = [10, 6, 12, 8, 9]
        for mode in ("greedy", "spec"):
            kw = dict(GEOM, mode=mode)
            base, _ = _run(_tiny_model(), prompts, new_lens, **kw)
            paged, eng = _run(_tiny_model(), prompts, new_lens,
                              **kw, **PAGED)
            assert base == paged, mode
            # retirement returned every live block; shared-prefix
            # chains may park evictable for the next identical prompt
            assert eng._kv.live_tokens() == 0
            assert eng._kv.blocks_used() == eng._kv.evictable_count()
            # n-gram spec rewind invariant: every rejected draft
            # row's over-allocation was rolled back by the length
            # rewind — no outstanding reservations survive the
            # drain, and prompt-lookup drafting (no resident draft
            # model) never touches the draft tenant's accounting
            assert eng._kv.outstanding() == 0
            assert eng._kv.draft_blocks_used() == 0

    def test_token_budget_defers_then_completes(self):
        # pool = ONE full-length request (8 blocks): each 60-token prompt
        # reserves ~5, so token-budget admission must serialize the three
        # requests — defer, never crash — and outputs still match dense
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, 200, size=60).tolist() for _ in range(3)]
        new_lens = [10, 10, 10]
        kw = dict(GEOM, batch_size=2)
        base, _ = _run(_tiny_model(), prompts, new_lens, **kw)
        paged, eng = _run(_tiny_model(), prompts, new_lens, **kw,
                          kv_block=16, max_live_tokens=128)
        assert base == paged
        assert eng._kv.num_blocks == 8

    def test_prefix_reuse_metrics_and_recorder(self):
        rng = np.random.default_rng(7)
        sys_prompt = rng.integers(1, 200, size=40).tolist()
        prompts = [sys_prompt + rng.integers(1, 200, size=int(k)).tolist()
                   for k in rng.integers(3, 9, size=6)]
        reg = MetricsRegistry()
        eng = ServingEngine(_tiny_model(), batch_size=4, max_len=128,
                            decode_chunk=16, prefill_chunk=16, kv_block=16,
                            max_live_tokens=4 * 96,
                            registry=reg)
        for p in prompts:
            eng.submit(Request(p, 6))
        eng.run()
        lbl = dict(policy="continuous")
        reuse = reg.get("serving_prefix_reuse_tokens_total"
                        ).labels(**lbl).value
        total = reg.get("serving_prompt_tokens_total").labels(**lbl).value
        # the first four prompts admit concurrently (nothing registered
        # yet), so only the two late admissions can adopt the 40-token
        # system prefix — 2 full blocks of 16 each
        assert reuse >= 2 * 32 and total == sum(len(p) for p in prompts)
        assert reg.get("serving_kv_blocks_used").labels(
            model="target", **lbl).value == eng._kv.blocks_used() > 0
        assert reg.get("serving_kv_blocks_free").labels(**lbl).value \
            == eng._kv.free_count()
        assert reg.get("serving_live_tokens").labels(**lbl).value == 0
        kinds = {e["kind"] for e in eng.recorder.snapshot(last=4096)
                 ["events"]}
        assert {"block_alloc", "block_free", "prefix_hit"} <= kinds

    def test_warm_paged_engine_zero_retraces(self):
        # one engine warms the compiled programs — every width of the
        # prefill run ladder in its FIRST prefill-spending step, whatever
        # lengths its own wave draws; a second runs a staggered wave with
        # hits (suffix runs of one chunk), misses (runs of two),
        # evictions (small pool), and chain growth — table values change
        # every step, shapes never
        rng = np.random.default_rng(7)
        sys_prompt = rng.integers(1, 200, size=40).tolist()

        def wave(n):
            return [sys_prompt
                    + rng.integers(1, 200, size=int(k)).tolist()
                    for k in rng.integers(3, 9, size=n)]

        model = _tiny_model()
        kw = dict(batch_size=4, max_len=128, decode_chunk=16,
                  prefill_chunk=16, kv_block=16, max_live_tokens=4 * 96,
                  instrument=False, recorder=False)
        eng = ServingEngine(model, **kw)
        for p in wave(6):
            eng.submit(Request(p, 6))
        eng.step()
        assert eng._widths == [1, 2] and eng._widths_warm
        eng.run()
        eng2 = ServingEngine(model, **kw)
        with assert_no_retrace():
            for p in wave(10):
                eng2.submit(Request(p, 8))
            eng2.run()

    def test_identical_prompt_readmitted_skips_prefill_chunks(self):
        # second submission of the same prompt adopts the cached chain:
        # fewer prefill chunks dispatch, outputs stay byte-identical
        rng = np.random.default_rng(9)
        prompt = rng.integers(1, 200, size=50).tolist()
        reg = MetricsRegistry()
        eng = ServingEngine(_tiny_model(), batch_size=2, max_len=128,
                            decode_chunk=16, prefill_chunk=16, kv_block=16,
                            max_live_tokens=2 * 128, registry=reg)
        lbl = dict(policy="continuous")

        def chunks():
            return reg.get("serving_prefill_chunks_total"
                           ).labels(**lbl).value

        r1 = eng.submit(Request(prompt, 8))
        eng.run()
        cold = chunks()
        r2 = eng.submit(Request(prompt, 8))
        eng.run()
        assert list(r2.output_ids) == list(r1.output_ids)
        # 48 of 50 tokens came from cache: one suffix chunk vs four
        assert chunks() - cold < cold
        assert reg.get("serving_prefix_reuse_tokens_total"
                       ).labels(**lbl).value == 48


# ---------------------------------------------------------------------------
# full parity matrix (slow): more prompts, mixed block/chunk geometries
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestPagedParityMatrix:
    def test_modes_pipelines_shared_prefixes(self):
        rng = np.random.default_rng(3)
        prompts = _shared_prefix_prompts(
            rng, (7, 19, 33, 12, 25, 9, 40, 15), share=(2, 4, 6))
        new_lens = [10, 6, 12, 8, 9, 7, 11, 5]
        for mode in ("greedy", "spec"):
            kw = dict(GEOM, mode=mode)
            base, _ = _run(_tiny_model(), prompts, new_lens, **kw)
            paged, _ = _run(_tiny_model(), prompts, new_lens,
                            **kw, **PAGED)
            assert base == paged, mode

    @pytest.mark.parametrize("kv_block", [8, 32])
    def test_block_chunk_geometry_variants(self, kv_block):
        # kv_block strictly smaller and strictly larger than the 16-token
        # prefill chunk (one must divide the other)
        rng = np.random.default_rng(3)
        prompts = _shared_prefix_prompts(rng, (7, 19, 33, 12, 25),
                                         share=(2, 4))
        new_lens = [10, 6, 12, 8, 9]
        kw = dict(GEOM, mode="greedy")
        base, _ = _run(_tiny_model(), prompts, new_lens, **kw)
        paged, _ = _run(_tiny_model(), prompts, new_lens, **kw,
                        kv_block=kv_block, max_live_tokens=3 * 128)
        assert base == paged
