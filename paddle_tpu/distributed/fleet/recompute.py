"""Activation recompute (reference: python/paddle/distributed/fleet/recompute/recompute.py
— RecomputeFunction:124, recompute():455).

TPU-native: rematerialization is a compiler feature — ``jax.checkpoint`` (jax.remat)
marks the region and XLA recomputes activations in backward.  The eager tape wraps the
rematerialized function as one GradNode, so ``.backward()`` sees a single op whose vjp
re-runs the forward — semantically identical to the reference's PyLayer."""
from __future__ import annotations

import jax

from paddle_tpu.autograd import engine as _engine
from paddle_tpu.tensor.tensor import Tensor

__all__ = ["recompute", "recompute_sequential", "recompute_hybrid"]


_POLICIES = {
    "full": None,  # save only the region inputs, recompute everything
    # save matmul/conv outputs: backward recomputes only cheap elementwise
    "dots": "dots_saveable",
    "dots_no_batch": "dots_with_no_batch_dims_saveable",
}


def resolve_policy(policy):
    """``None`` / ``"full"``: keep the region's inputs only.  A tuple of
    ``jax.ad_checkpoint.checkpoint_name`` names: keep those values beside
    the inputs (how ``models/llama.py`` keeps the attention kernel's
    residuals).  ``"dots"`` / ``"dots_no_batch"``: jax's policies."""
    if policy is None:
        return None
    if isinstance(policy, tuple):
        return jax.checkpoint_policies.save_only_these_names(*policy)
    if policy == "named":
        raise ValueError(
            "recompute policy 'named' is gone: LlamaConfig(recompute=True) "
            "keeps the attention kernel's output and log-sum-exp by default "
            "(recompute_policy=None); 'full' keeps the layer inputs only")
    if policy not in _POLICIES:
        raise ValueError(
            f"unknown recompute policy {policy!r}; one of {sorted(_POLICIES)}")
    key = _POLICIES[policy]
    return None if key is None else getattr(jax.checkpoint_policies, key)


def recompute(function, *args, **kwargs):
    use_reentrant = kwargs.pop("use_reentrant", True)  # noqa: F841 (API parity)
    preserve_rng_state = kwargs.pop("preserve_rng_state", True)  # noqa: F841
    policy = resolve_policy(kwargs.pop("policy", None))

    fn = function.forward if hasattr(function, "forward") else function

    tensor_args = [a for a in args if isinstance(a, Tensor)]
    other = [(i, a) for i, a in enumerate(args) if not isinstance(a, Tensor)]

    def raw(*xs):
        xs = list(xs)
        full = []
        ti = 0
        oi = dict(other)
        for i in range(len(args)):
            if i in oi:
                full.append(oi[i])
            else:
                full.append(Tensor(xs[ti]))
                ti += 1
        out = fn(*full, **kwargs)
        return jax.tree_util.tree_map(
            lambda t: t.data if isinstance(t, Tensor) else t, out,
            is_leaf=lambda t: isinstance(t, Tensor),
        )

    ck = jax.checkpoint(raw, policy=policy)
    return _engine.apply("recompute", lambda *xs: ck(*xs), *tensor_args)


def recompute_sequential(ctx, functions, *args, **kwargs):
    segments = ctx.get("segments", 1) if isinstance(ctx, dict) else 1
    funcs = list(functions)
    n = len(funcs)
    seg = max(n // max(segments, 1), 1)
    out = args
    i = 0
    while i < n:
        chunk = funcs[i : i + seg]

        def run_chunk(*xs, _chunk=chunk):
            y = xs
            for f in _chunk:
                y = f(*y) if isinstance(y, tuple) else f(y)
                if not isinstance(y, tuple):
                    y = (y,)
            return y[0] if len(y) == 1 else y

        out = recompute(run_chunk, *(out if isinstance(out, tuple) else (out,)), **kwargs)
        if not isinstance(out, tuple):
            out = (out,)
        i += seg
    return out[0] if isinstance(out, tuple) and len(out) == 1 else out


def recompute_hybrid(ctx, function, *args, **kwargs):
    return recompute(function, *args, **kwargs)
