"""Functionalize a Layer + loss + Optimizer into one jit-compiled train step.

This is the TPU-native analog of the reference's static-graph lowering
(python/paddle/base/executor.py + jit/to_static): instead of capturing a ProgramDesc,
the eager Layer is run once under ``jax.jit`` tracing with its parameters/buffers/
optimizer accumulators passed as pytree arguments, producing ONE fused XLA program for
forward+backward+update per step (the CinnJitInstruction analog, SURVEY.md §2.5).

Sharded parameters (mp_layers, group_sharded, shard_tensor) keep their NamedShardings —
pjit propagates them through the step, so the same TrainStep object serves single-chip
and full tp/pp/dp/sharding meshes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.autograd import engine as _engine
from paddle_tpu.observability.compilecache import CompileCacheMonitor, phase
from paddle_tpu.observability.metrics import get_registry
from paddle_tpu.observability.trace import span
from paddle_tpu.tensor.tensor import Tensor

__all__ = ["TrainStep", "build_train_step", "build_eval_fn"]

# observability: the fused train/eval programs are THE compile cache of the
# training stack — a retrace per step (shape churn in the data pipeline, a
# replaced optimizer) is a recompile storm that only shows as wall-clock
# without these series.  Dispatches land in compile_cache_{hits,misses}_total
# {cache="functionalize"} + compile_seconds; every step also counts into
# train_steps_total, and its dispatch runs under a "train.step" span (an
# event of the profiler's timeline, beside the device lines, when a
# jax.profiler session is on).  The dispatch is asynchronous: the span
# times the enqueue, never the step — the step's device time is the XLA
# module's, and its parts carry the names of observability.trace.SCOPES.
_mon = CompileCacheMonitor("functionalize")
_train_steps = get_registry().counter(
    "train_steps_total", "fused train-step dispatches")


class _ClipStub:
    """Parameter stand-in handed to grad-clip callables inside the traced
    step — carries the attributes clip implementations consult (need_clip,
    plus name/shape/dtype for user subclasses that branch on them)."""

    __slots__ = ("need_clip", "name", "shape", "dtype")

    def __init__(self, need_clip, name="", shape=None, dtype=None):
        self.need_clip = need_clip
        self.name = name
        self.shape = shape
        self.dtype = dtype


def _apply_clip(clip, grads, stubs):
    """Run a grad-clip object over a {name: array} grad tree inside the trace
    (None entries = frozen params, passed through untouched)."""
    keys = [k for k, g in grads.items() if g is not None]
    pgs = [(stubs[k], Tensor(grads[k])) for k in keys]
    clipped = clip(pgs)
    out = dict(grads)
    for k, (_, t) in zip(keys, clipped):
        out[k] = t.data if isinstance(t, Tensor) else t
    return out


class TrainStep:
    """Callable ``step(*inputs, label) -> loss``.  Holds the functional state
    (params/buffers/accumulators) and keeps the Layer's Parameters pointed at the
    latest arrays after every step (reference users read ``layer.state_dict()``
    mid-training)."""

    def __init__(self, network, loss_fn, optimizer, recompute=False, donate=True,
                 amp_level=None, amp_dtype="bfloat16"):
        self._network = network
        self._loss_fn = loss_fn
        self._optimizer = optimizer
        self._recompute = recompute
        # amp_level "O1"/"O2" wraps the traced forward in amp.auto_cast — the
        # per-op white/black-list casting at the apply() chokepoint happens at
        # trace time, so the compiled program runs white-list matmuls in
        # amp_dtype exactly like eager autocast.
        self._amp_level = None if amp_level in (None, "O0") else amp_level
        self._amp_dtype = amp_dtype
        self._params, self._buffers = network.functional_state()
        # Mirror the eager optimizer's params_grads construction
        # (optimizer.py:122): frozen params never enter clipping or updates.
        self._trainable = {
            n: (not getattr(p, "stop_gradient", False)
                and getattr(p, "trainable", True))
            for n, p in network.named_parameters()
        }
        self._clip_stubs = {
            n: _ClipStub(bool(getattr(p, "need_clip", True)), name=n,
                         shape=list(p.shape), dtype=p.dtype)
            for n, p in network.named_parameters()
        }
        # initial param layouts (TP etc.) — ZeRO constraints compose with
        # these instead of clobbering them
        from jax.sharding import NamedSharding as _NS

        self._param_specs = {
            k: a.sharding.spec
            for k, a in self._params.items()
            if isinstance(getattr(a, "sharding", None), _NS)
        }
        self._states = (
            optimizer.functional_init_states(self._params)
            if optimizer is not None
            else {}
        )
        self._step_count = int(getattr(optimizer, "_global_step", 0) or 0)
        donate_argnums = (0, 2) if donate else ()
        self._jitted = jax.jit(self._step_fn, donate_argnums=donate_argnums)

    # -- traced once per (shapes, dtypes, shardings) --------------------------------
    def _step_fn(self, params, buffers, states, lr, step, *datas):
        _mon.mark_trace("train_step")
        network, loss_fn, optimizer = self._network, self._loss_fn, self._optimizer

        import contextlib

        if self._amp_level is not None:
            from paddle_tpu.amp.auto_cast import auto_cast as _auto_cast

            amp_ctx = lambda: _auto_cast(level=self._amp_level,
                                         dtype=self._amp_dtype)
        else:
            amp_ctx = contextlib.nullcontext

        def loss_of(ps):
            # the eager tape is bypassed (no_grad): ops execute their jnp bodies
            # directly as traced ops; jax.value_and_grad supplies the gradients.
            with _engine.no_grad(), amp_ctx():
                inputs = [Tensor(d) for d in datas]
                if loss_fn is not None:
                    out = network.functional_call(ps, buffers, *inputs[:-1])
                    l = loss_fn(out, inputs[-1])
                else:
                    out = network.functional_call(ps, buffers, *inputs)
                    l = out
            return l.data if isinstance(l, Tensor) else l

        fwd = jax.checkpoint(loss_of) if self._recompute else loss_of
        lval, grads = jax.value_and_grad(fwd)(params)

        # Frozen params get None grads (functional_update passes them through
        # untouched; XLA DCEs their backward computation) — same exclusion the
        # eager path applies when building params_grads.
        grads = {
            k: (g if self._trainable.get(k, True) else None)
            for k, g in grads.items()
        }

        # gradient_scale_configs.scale_strategy "sum": un-average the
        # dp-mean grads (fleet.distributed_optimizer sets _grad_rescale)
        rescale = float(getattr(optimizer, "_grad_rescale", 1.0) or 1.0)
        if rescale != 1.0:
            grads = {k: (g * rescale if g is not None else None)
                     for k, g in grads.items()}

        # Grad clipping: run the clip object's OWN _dygraph_clip inside the
        # trace (every built-in clip is pure jnp, hence traceable) so the
        # compiled step has identical semantics to eager for ClipGradByValue
        # (elementwise), ClipGradByNorm (per-tensor), ClipGradByGlobalNorm
        # (one fused norm), and any user subclass — reference
        # python/paddle/nn/clip.py applies the same objects on both paths.
        # When the optimizer ACCUMULATES (GradientMergeOptimizer k_steps>1 /
        # DistributedFusedLamb gradient_accumulation_steps>1), the reference
        # clips the MERGED gradient once at apply time, not each micro-grad —
        # hand the traced clip to functional_update instead.
        clip = getattr(optimizer, "_grad_clip", None)
        merge_k = max(int(getattr(optimizer, "k_steps", 1) or 1),
                      int(getattr(optimizer, "_acc_steps", 1) or 1))
        # always reset: a stale hook from a previous TrainStep (different
        # network / clip since removed) must never survive into this trace
        optimizer._merged_clip = None
        if clip is not None:
            if merge_k > 1:
                stubs = self._clip_stubs  # capture only (clip, stubs), not self
                optimizer._merged_clip = functools.partial(
                    _apply_clip, clip, stubs=stubs)
            else:
                grads = _apply_clip(clip, grads, self._clip_stubs)

        # ZeRO stage-2: constrain each grad to the accumulators' sharded
        # layout at the point the update consumes it — the update then runs
        # at shard shape (only grad shards stay live) and XLA lowers the grad
        # reduction to reduce-scatter where its combiner exists (TPU), or
        # all-reduce + slice elsewhere.  distributed/sharding/__init__.py.
        gs_level = getattr(optimizer, "_group_sharded_level", 0)

        def zero_constrain(tree):
            from jax.sharding import NamedSharding

            from paddle_tpu.distributed.sharding import leading_dim_spec

            mesh, axis = optimizer._gs_mesh, optimizer._gs_axis
            return {
                k: (v if v is None else jax.lax.with_sharding_constraint(
                    v, NamedSharding(mesh, leading_dim_spec(
                        v.shape, mesh, axis, base=self._param_specs.get(k)))))
                for k, v in tree.items()
            }

        if gs_level >= 2 and getattr(optimizer, "_gs_mesh", None) is not None:
            grads = zero_constrain(grads)

        prev = optimizer._global_step
        optimizer._global_step = step  # bias-correction uses the traced step counter
        try:
            with jax.named_scope("optimizer"):
                new_params, new_states = optimizer.functional_update(
                    params, grads, states, lr)
        finally:
            optimizer._global_step = prev

        # ZeRO stage-3: keep updated params sharded across steps (without the
        # constraint XLA may choose replicated outputs, silently reverting the
        # parameter layout stage 3 is about)
        if gs_level >= 3 and getattr(optimizer, "_gs_mesh", None) is not None:
            new_params = zero_constrain(new_params)
        return lval, new_params, new_states

    def _operands(self, step_count, datas):
        arrs = [d.data if isinstance(d, Tensor) else jnp.asarray(d) for d in datas]
        lr = jnp.asarray(self._optimizer.get_lr(), jnp.float32)
        step = jnp.asarray(step_count, jnp.int32)
        return (self._params, self._buffers, self._states, lr, step, *arrs)

    def lower(self, *datas):
        """The step program lowered from the live operands, exactly as the
        next ``__call__`` would pass them: ``.compile()`` of the result is
        the program the steps run (a compile-cache hit once a step has
        run).  Nothing is donated, run or counted."""
        return self._jitted.lower(*self._operands(self._step_count + 1, datas))

    def __call__(self, *datas):
        self._step_count += 1
        operands = self._operands(self._step_count, datas)
        _train_steps.inc()
        with span("train.step", step=self._step_count):
            lval, self._params, self._states = _mon.call(
                "train_step", self._jitted, *operands)
        # FLAGS_check_nan_inf on the fused path: one loss readback per step
        # (per-op checking is impossible inside a compiled program; a
        # non-finite loss is the canonical divergence signal the reference's
        # nan_inf_utils surfaces).  No overhead when the flag is unset.
        from paddle_tpu.autograd.engine import _nan_check_enabled

        if _nan_check_enabled():
            import numpy as _np

            lv = _np.asarray(lval)
            if not _np.all(_np.isfinite(lv)):
                raise RuntimeError(
                    f"[check_nan_inf] op=train_step: non-finite loss {lv} at "
                    f"global step {self._step_count} — enable "
                    "amp.debugging.enable_tensor_checker() and run eagerly "
                    "to localize the producing op"
                )
        for n, p in self._network.named_parameters():
            if n in self._params:
                p._data = self._params[n]  # pointer swap, no device copy
        sched = getattr(self._optimizer, "_lr_scheduler", None)
        if sched is not None:
            sched.step()
        return Tensor(lval)

    def state_dict(self):
        return {n: Tensor(a) for n, a in {**self._params, **self._buffers}.items()}

    @property
    def params(self):
        """The step's live parameters, ``{name: jax array}`` as of the last
        step dispatched (read-only view: a copy of the mapping, not of the
        arrays).  With ``donate=True`` the arrays are consumed by the next
        step — read them between steps, do not hold them across one."""
        return dict(self._params)

    @property
    def optimizer_states(self):
        """The optimizer's functional state, ``{accumulator: {name: jax
        array}}`` (``moment1``, ``moment2``, and for quantized moments
        their ``@scale`` siblings) as of the last step dispatched — same
        read-only contract as :attr:`params`."""
        return {k: dict(v) if isinstance(v, dict) else v
                for k, v in self._states.items()}


def amp_args_from_strategy(strategy):
    """(amp_level, amp_dtype) from an auto-parallel Strategy-style config bag
    — the one place the amp knob is interpreted, shared by Engine, DistModel
    and any other build_train_step caller."""
    amp = getattr(strategy, "amp", None)
    if not getattr(amp, "enable", False):
        return None, "bfloat16"
    return getattr(amp, "level", "O1") or "O1", getattr(amp, "dtype", "bfloat16")


def build_train_step(network, loss_fn, optimizer, recompute=False, donate=True,
                     amp_level=None, amp_dtype="bfloat16"):
    # the step's construction (the functional state, the optimizer's
    # accumulators) is a phase of the start-up record; its first call is
    # the ``first_call`` entry of ``functionalize/train_step``
    with phase("train.build"):
        return TrainStep(network, loss_fn, optimizer, recompute=recompute,
                         donate=donate, amp_level=amp_level,
                         amp_dtype=amp_dtype)


def build_eval_fn(network, loss_fn=None):
    """jit-compiled forward (plus loss) with parameters passed functionally."""
    params, buffers = network.functional_state()

    @jax.jit
    def eval_fn(params, buffers, *datas):
        _mon.mark_trace("eval")
        with _engine.no_grad():
            inputs = [Tensor(d) for d in datas]
            if loss_fn is not None:
                out = network.functional_call(params, buffers, *inputs[:-1])
                out = loss_fn(out, inputs[-1])
            else:
                out = network.functional_call(params, buffers, *inputs)
        return jax.tree_util.tree_map(
            lambda t: t.data if isinstance(t, Tensor) else t, out,
            is_leaf=lambda t: isinstance(t, Tensor),
        )

    def run(*datas):
        arrs = [d.data if isinstance(d, Tensor) else jnp.asarray(d) for d in datas]
        p, b = network.functional_state()
        out = _mon.call("eval", eval_fn, p, b, *arrs)
        return jax.tree_util.tree_map(Tensor, out)

    # expose the jitted callable + live state for cost analysis
    # (auto_parallel Engine.cost lowers it with XLA's cost model)
    run._jitted = eval_fn
    run._network = network
    return run
