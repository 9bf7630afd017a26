"""What a recomputed Llama layer's checkpoint keeps (ISSUE 32).

``LlamaConfig(recompute=True)`` checkpoints each layer with
``save_only_these_names(<kept residuals of the attention kernel>)``: the
flash custom VJPs' forward rules name what their backward reads
(``observability.trace.ATTN_RESIDUALS``), and with ``out`` + ``lse`` kept
the recompute inside the backward has no use for the forward kernel.
``recompute_policy="full"`` keeps the layer inputs only and runs it again.

The decision is made at trace time, so the count of forward kernels in the
gradient's jaxpr IS the engagement counter: one a layer under the default,
two under ``"full"``.  A kept ``out`` is the value the second run would have
produced, so losses and gradients are equal to the bit.  The flash path runs
here in interpret mode behind a patched gate; the plain-``jnp`` path
(``_sdpa_ref``, no custom VJP) follows the same rule through the name
``F.scaled_dot_product_attention`` puts on the fallback's output.
"""
import collections
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.ops.fused_rope  # noqa: F401  (binds the real gate BEFORE the patch below: a first import under it would keep the patched one)
from paddle_tpu.autograd import engine as _engine
from paddle_tpu.distributed.fleet.recompute import recompute
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models import llama
from paddle_tpu.observability.trace import ATTN_RESIDUALS
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.tensor.tensor import Tensor

LAYERS, SEQ = 2, 128
FWD = "flash_attention_fwd"


def _count(jaxpr, out):
    """Pallas kernels by name, and dots, of a jaxpr and all it holds."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"]] += 1
            continue
        if eqn.primitive.name == "dot_general":
            out["dot"] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count(sub, out)
    return out


@functools.lru_cache(maxsize=None)
def _gradient(policy, flash):
    """(kernel and dot counts of the gradient's jaxpr, loss, gradients) of
    a two-layer step; head dim 128 and 128 positions, the least the flash
    gate takes."""
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=256, intermediate_size=128,
        num_hidden_layers=LAYERS, num_attention_heads=2,
        num_key_value_heads=1, max_position_embeddings=SEQ, dtype="float32",
        recompute=True, recompute_policy=policy))
    params, buffers = model.functional_state()
    ids = Tensor(jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (1, SEQ))))

    def loss_of(ps):
        with _engine.no_grad():
            return model.functional_call(ps, buffers, ids, ids).data

    grad = jax.value_and_grad(loss_of)
    with mock.patch.object(fa, "_on_tpu", lambda: flash), \
            mock.patch.object(
                fa, "flash_attention_blhd",
                functools.partial(fa.flash_attention_blhd, interpret=True)):
        counts = _count(jax.make_jaxpr(grad)(params).jaxpr,
                        collections.Counter())
        loss, grads = jax.jit(grad)(params)
    return counts, np.asarray(loss), jax.tree.map(np.asarray, grads)


@pytest.mark.parametrize("policy,per_layer", [(None, 1), ("full", 2)],
                         ids=["default", "full"])
def test_forward_kernels_in_the_gradient(policy, per_layer):
    counts, _, _ = _gradient(policy, True)
    assert counts[FWD] == per_layer * LAYERS
    for kernel in ("_bwd_delta", "_bwd_dkv", "_bwd_dq"):
        assert counts["flash_attention" + kernel] == LAYERS


@pytest.mark.parametrize("flash", [True, False], ids=["flash", "sdpa_ref"])
def test_default_equals_full_to_the_bit(flash):
    kept, full = _gradient(None, flash), _gradient("full", flash)
    assert (kept[0][FWD] > 0) == flash
    assert kept[1] == full[1]
    leaves = jax.tree.leaves_with_path(kept[2])
    assert len(leaves) == 3 + 9 * LAYERS
    for (path, a), b in zip(leaves, jax.tree.leaves(full[2])):
        assert np.array_equal(a, b), jax.tree_util.keystr(path)


def test_plain_attention_keeps_its_output():
    """No custom VJP: the kept output spares the recompute the
    probabilities' product with V (one dot a layer), nothing else."""
    kept, full = _gradient(None, False)[0], _gradient("full", False)[0]
    assert full["dot"] - kept["dot"] == LAYERS


def test_kept_set_is_the_kernels_output_and_lse():
    assert llama._RECOMPUTE_KEEPS == ATTN_RESIDUALS[3:] \
        == ("attn.res.out", "attn.res.lse")


@pytest.mark.parametrize("rule", ["packed", "segmented", "rope"])
def test_forward_rules_name_every_residual(rule):
    """Each custom VJP's forward rule names its five residuals, and hands
    back as primal output the SAME named ``out`` it keeps."""
    b, l, h, hkv, d = 1, 128, 2, 1, 128
    q = jnp.ones((b, l, h * d), jnp.float32)
    kv = jnp.ones((b, l, hkv * d), jnp.float32)
    seg = jnp.zeros((b, l), jnp.int32)
    table = jnp.ones((l, d), jnp.float32)
    fwd, args = {
        "packed": (fa._fap_fwd, (q, kv, kv)),
        "segmented": (fa._faps_fwd, (q, kv, kv, seg, seg)),
        "rope": (fa._fapr_fwd, (q, kv, kv, table, table)),
    }[rule]
    jaxpr = jax.make_jaxpr(
        lambda *a: fwd(*a, h, hkv, True, None, True))(*args).jaxpr
    named = {e.params["name"]: e.outvars[0] for e in jaxpr.eqns
             if e.primitive.name == "name"}
    assert tuple(named) == ATTN_RESIDUALS
    assert jaxpr.outvars[0] is named["attn.res.out"]
    assert sum(v is named["attn.res.out"] for v in jaxpr.outvars) == 2
    for var in named.values():
        assert var in jaxpr.outvars


@pytest.mark.parametrize("policy", ["named", "ckpt", "nothing"])
def test_unknown_and_removed_policies_are_refused(policy):
    x = paddle.to_tensor(np.ones((2, 2), np.float32))
    match = "gone" if policy == "named" else "unknown recompute policy"
    with pytest.raises(ValueError, match=match):
        recompute(lambda a: a * 2, x, policy=policy)
    with pytest.raises(ValueError, match=match):
        LlamaConfig.tiny(recompute=True, recompute_policy=policy)


@pytest.mark.parametrize("policy", [None, "full", "dots", "dots_no_batch",
                                    ("attn.res.out",)],
                         ids=lambda p: str(p))
def test_policies_that_stay_give_the_plain_gradient(policy):
    w = np.arange(6, dtype=np.float32).reshape(2, 3)

    def grad_of(wrap):
        x = paddle.to_tensor(w, stop_gradient=False)
        wrap(lambda a: (a * a).sum(), x).backward()
        return np.asarray(x.grad.data)

    assert np.array_equal(
        grad_of(functools.partial(recompute, policy=policy)),
        grad_of(lambda f, x: f(x)))
