"""Tensor-parallel sharded serving (paddle_tpu/serving/sharding.py).

The acceptance property on the virtual CPU mesh at f32: a mesh-placed
engine's token streams are BYTE-IDENTICAL to the single-device engine on
the same workload, across greedy/spec x one-chunk / many-chunk
prefill — and the warm sharded path runs with zero retraces.  Per-layer
activations are NOT bitwise under TP (the row-parallel psum reassociates
the contraction), but greedy argmax at f32 absorbs the ~1e-5 wobble, so
the emitted tokens match exactly; this file pins that contract.
"""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as PS

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.llama_decode import _decode_params_of
from paddle_tpu.serving import Request, ServingEngine
from paddle_tpu.serving.sharding import (
    kv_cache_pspec, llama_tp_rules, match_partition_rules,
    shard_decode_params,
)

N_TP = 4


def _mesh(n=N_TP):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (force with "
                    "--xla_force_host_platform_device_count)")
    return Mesh(np.array(jax.devices()[:n]), ("mp",))


def _tp_model(seed=0):
    # tiny() has nkv=2 — bump to 4 so heads divide the 4-way mesh axis
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(num_key_value_heads=4, dtype="float32")
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


def _run(model, prompts, new_lens, **kw):
    eng = ServingEngine(model, **kw)
    for p, n in zip(prompts, new_lens):
        eng.submit(Request(p, int(n)))
    done = eng.run()
    assert not eng.has_work
    return {r.rid: r for r in done}


class TestPartitionRules:
    def test_every_llama_param_matched(self):
        model = _tp_model()
        params, _ = _decode_params_of(model, 64)
        specs = match_partition_rules(llama_tp_rules(), params)
        # column-parallel attention/MLP, row-parallel returns, replicated
        # embeddings/norms — spot-check one of each family
        layer = specs["layers"][0]
        assert layer["wq"] == PS(None, "mp")
        assert layer["gate"] == PS(None, "mp")
        assert layer["wo"] == PS("mp", None)
        assert layer["down"] == PS("mp", None)
        assert specs["embed"] == PS()
        assert specs["lm_head"] == PS()
        assert layer["ln1"] == PS()

    def test_scalars_short_circuit_to_replicated(self):
        specs = match_partition_rules(
            llama_tp_rules(), {"anything": np.float32(2.0)})
        assert specs["anything"] == PS()

    def test_unmatched_nonscalar_raises(self):
        with pytest.raises(ValueError, match="no partition rule matched"):
            match_partition_rules(
                llama_tp_rules(), {"mystery": np.zeros((8, 8))})

    def test_first_match_wins(self):
        rules = ((r"wq", PS(None, "mp")), (r".*", PS()))
        specs = match_partition_rules(rules, {"wq": np.zeros((4, 4)),
                                              "other": np.zeros((4, 4))})
        assert specs["wq"] == PS(None, "mp") and specs["other"] == PS()


class TestShardPlacement:
    def test_params_and_cache_land_sharded(self):
        mesh = _mesh()
        model = _tp_model()
        params, _ = _decode_params_of(model, 64)
        sharded, specs = shard_decode_params(params, mesh)
        wq = sharded["layers"][0]["wq"]
        assert wq.sharding.spec == PS(None, "mp")
        assert sharded["embed"].sharding.spec == PS()
        assert kv_cache_pspec() == PS(None, None, "mp", None)
        eng = ServingEngine(model, batch_size=2, max_len=64, mesh=mesh)
        k0, _ = eng._kv.caches[0]
        assert k0.sharding.spec == kv_cache_pspec()

    def test_indivisible_heads_raise(self):
        mesh = _mesh()
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(dtype="float32"))  # nkv=2
        model.eval()
        with pytest.raises(ValueError):
            ServingEngine(model, batch_size=2, max_len=64, mesh=mesh)

    def test_bad_axis_name_raises(self):
        mesh = _mesh()
        with pytest.raises(ValueError, match="no axis"):
            ServingEngine(_tp_model(), batch_size=2, max_len=64,
                          mesh=mesh, tp_axis="dp")


class TestTPByteIdentity:
    """Sharded vs single-device token streams, exhaustive over the
    scheduler feature matrix (mode x chunking: the default chunk, clamped
    to ``max_len``, takes each prompt whole; 4 takes it in several)."""

    @pytest.mark.parametrize("mode,prefill_chunk", [
        ("greedy", 256),
        ("greedy", 4),
        ("spec", 4),
        ("spec", 256),
    ])
    def test_matches_single_device(self, mode, prefill_chunk):
        mesh = _mesh()
        model = _tp_model()
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 256, (p,)) for p in (5, 9, 6, 11)]
        new_lens = [6, 4, 8, 5]
        kw = dict(batch_size=2, max_len=64, mode=mode,
                  prefill_chunk=prefill_chunk)
        if mode == "spec":
            kw["spec_k"] = 4
        a = _run(model, prompts, new_lens, mesh=mesh, **kw)
        b = _run(model, prompts, new_lens, **kw)
        for i in a:
            np.testing.assert_array_equal(a[i].output_ids, b[i].output_ids)

    def test_warm_sharded_run_zero_retraces(self):
        from paddle_tpu.analysis import assert_no_retrace
        mesh = _mesh()
        model = _tp_model()
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, 256, (p,)) for p in (5, 8)]
        kw = dict(batch_size=2, max_len=64, mesh=mesh)
        _run(model, prompts, [4, 6], **kw)  # compile
        # a FRESH engine on the same mesh/config shares the process-wide
        # program cache — warm steps must not trace anything
        with assert_no_retrace():
            _run(model, prompts, [4, 6], **kw)

    @pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
    def test_q8_matches_single_device(self, paged):
        # the TP cell of the q8 parity matrix: int8 data shards over the
        # head axis and the f16 scale leaf rides PS(None, None, "mp") —
        # a mesh-placed q8 engine stays byte-identical to single-device
        # q8 (quantization happens per head AFTER the column-parallel
        # projection, so sharding never changes which values are scaled)
        mesh = _mesh()
        rng = np.random.default_rng(13)
        prompts = [rng.integers(1, 200, (p,)) for p in (5, 9, 7)]
        new_lens = [6, 4, 7]
        kw = dict(batch_size=2, max_len=64, kv_dtype="int8")
        if paged:
            kw.update(kv_block=16, max_live_tokens=2 * 64)
        a = _run(_tp_model(), prompts, new_lens, mesh=mesh, **kw)
        b = _run(_tp_model(), prompts, new_lens, **kw)
        for i in a:
            np.testing.assert_array_equal(a[i].output_ids, b[i].output_ids)

    def test_q8_scale_leaf_sharded(self):
        mesh = _mesh()
        eng = ServingEngine(_tp_model(), batch_size=2, max_len=64,
                            mesh=mesh, kv_dtype="int8")
        (kd, ks), _ = eng._kv.caches[0]
        assert kd.sharding.spec == PS(None, None, "mp", None)
        assert ks.sharding.spec == PS(None, None, "mp")

    @pytest.mark.parametrize("mode", ["greedy", "spec"])
    def test_paged_matches_single_device(self, mode):
        # paged + TP composes: the block pool shards over the head axis
        # (index 2 in both geometries), the table replicates, and the
        # shared-prefix workload exercises radix hits under the mesh
        mesh = _mesh()
        rng = np.random.default_rng(5)
        shared = rng.integers(1, 200, size=24).tolist()
        prompts = [shared + rng.integers(1, 200, size=int(k)).tolist()
                   for k in (5, 9, 3, 12, 7)]
        new_lens = [8, 6, 9, 5, 7]
        kw = dict(batch_size=3, max_len=128, mode=mode, decode_chunk=16,
                  prefill_chunk=16, kv_block=16, max_live_tokens=3 * 128,
                  instrument=False, recorder=False)
        a = _run(_tp_model(), prompts, new_lens, mesh=mesh, **kw)
        b = _run(_tp_model(), prompts, new_lens, **kw)
        for i in a:
            np.testing.assert_array_equal(a[i].output_ids, b[i].output_ids)
