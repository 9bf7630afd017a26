"""Declarative registry of the static axes that define a serving program.

Every knob that changes the *traced program* rather than its runtime
inputs — which attention kernel runs, how the KV cache and the decode
weights are stored, whether the row-parallel TP reduction is segmented —
is declared exactly once, here, as a :class:`StaticAxis` row of
:data:`PROGRAM_AXES`.  The frozen :class:`ProgramKey` dataclass carries
one value per axis and is the single static argument threaded through
the four ``models/llama_decode.py`` serving impls, ``serving/engine.py``,
and ``serving/sharding.py``'s TP program cache key.  Adding a new static
knob means adding one axis row and one field — not editing N
``static_argnames`` lists and M hand-built cache-key tuples.

tpu-lint's PTL014 (program-cache-key completeness) reads
:data:`PROGRAM_AXES` as the source of truth: a program-cache key that
hand-threads a *subset* of these axis names instead of carrying a
``program_key`` is an incomplete key and is flagged.

``ProgramKey`` is hashable and comparison-stable, so it is directly
usable as a jit ``static_argnames`` value and as a dict-key component:
two engines configured identically share compiled programs; any
differing axis forks the cache entry.
"""

from __future__ import annotations

import dataclasses

__all__ = ["StaticAxis", "PROGRAM_AXES", "ProgramKey"]


@dataclasses.dataclass(frozen=True)
class StaticAxis:
    """One static program axis: name, default, validation, and intent.

    ``values`` is the closed enum of allowed settings when ``kind`` is
    ``"enum"``; ``kind="segments"`` instead accepts ``None`` (off) or an
    ``int >= 2`` (the number of per-layer reduction segments);
    ``kind="depth"`` accepts ``None`` (off) or an ``int >= 1`` (a draft
    depth — the number of speculative candidate tokens per round).
    """

    name: str
    default: object
    doc: str
    values: tuple = ()
    kind: str = "enum"

    def validate(self, value):
        if self.kind == "enum":
            if value not in self.values:
                allowed = ", ".join(repr(v) for v in self.values)
                raise ValueError(
                    f"ProgramKey: unknown {self.name} {value!r}; expected "
                    f"one of ({allowed}).  {self.doc}")
            return value
        if self.kind == "segments":
            if value is None:
                return None
            if isinstance(value, bool) or not isinstance(value, int) or value < 2:
                raise ValueError(
                    f"ProgramKey: {self.name} must be None (off) or an "
                    f"int >= 2 (segments per row-parallel reduction), got "
                    f"{value!r}.  {self.doc}")
            return value
        if self.kind == "depth":
            if value is None:
                return None
            if isinstance(value, bool) or not isinstance(value, int) or value < 1:
                raise ValueError(
                    f"ProgramKey: {self.name} must be None (off) or an "
                    f"int >= 1 (draft tokens per speculative round), got "
                    f"{value!r}.  {self.doc}")
            return value
        raise AssertionError(f"unknown StaticAxis kind {self.kind!r}")


#: THE registry.  One row per static knob; every consumer (the serving
#: impls' ``program_key`` static, the engine's constructor kwargs, the TP
#: program-cache key, PTL014) derives from this tuple.
PROGRAM_AXES = (
    StaticAxis(
        "attn_impl", None,
        "decode-time cache-read attention: None/'reference' = XLA flash "
        "loop, 'pallas' = fused VMEM-resident kernel with reference "
        "fallback when unsupported.",
        values=(None, "reference", "pallas")),
    StaticAxis(
        "prefill_impl", None,
        "chunked-prefill attention + KV append: None/'reference' = flash "
        "loop plus separate quantize-on-append scatter, 'pallas' = one "
        "fused kernel (attention + in-kernel append) with reference "
        "fallback when unsupported.",
        values=(None, "reference", "pallas")),
    StaticAxis(
        "kv_dtype", None,
        "KV cache storage override: None keeps the model dtype, 'int8' "
        "selects the quantized cache (f16 absmax scale leaf).",
        values=(None, "int8")),
    StaticAxis(
        "weight_dtype", None,
        "decode matmul weight storage: None keeps the checkpoint dtype, "
        "'int8' selects per-output-channel symmetric quantization.",
        values=(None, "int8")),
    StaticAxis(
        "tp_overlap", None,
        "segment the row-parallel (wo/down) matmul + psum along the "
        "output-feature axis so per-segment collectives can overlap "
        "trailing compute; byte-identical math, different schedule.",
        kind="segments"),
    StaticAxis(
        "draft_source", None,
        "speculative draft generator: None = not speculating (greedy), "
        "'prompt_lookup' = n-gram continuation mined from the slot's "
        "token history, 'draft_model' = a resident shrunk-llama draft "
        "model decoding k candidates through its own compiled program.",
        values=(None, "prompt_lookup", "draft_model")),
    StaticAxis(
        "spec_depth", None,
        "draft tokens verified per speculative round (the k in the "
        "[B, k+1] verify forward); each depth is its own compiled "
        "program, so the adaptive-k ladder pre-warms one entry per rung.",
        kind="depth"),
    StaticAxis(
        "spec_tree", None,
        "tree-structured candidates: None = linear draft chain, 'top2' = "
        "top-2 branch at the first draft position verified in the same "
        "batched forward through a tree attention mask (draft_model + "
        "dense caches only).",
        values=(None, "top2")),
)

_AXES_BY_NAME = {ax.name: ax for ax in PROGRAM_AXES}


@dataclasses.dataclass(frozen=True)
class ProgramKey:
    """One frozen, hashable value per :data:`PROGRAM_AXES` row.

    Field order and names mirror the registry; ``__post_init__`` runs each
    axis's validator so an invalid knob fails loudly at construction —
    never as an opaque trace error inside the first compiled step.
    """

    attn_impl: object = None
    prefill_impl: object = None
    kv_dtype: object = None
    weight_dtype: object = None
    tp_overlap: object = None
    draft_source: object = None
    spec_depth: object = None
    spec_tree: object = None

    def __post_init__(self):
        for ax in PROGRAM_AXES:
            ax.validate(getattr(self, ax.name))

    def axes(self):
        """(name, value) pairs in registry order — for logs and metrics."""
        return tuple((ax.name, getattr(self, ax.name)) for ax in PROGRAM_AXES)

    def replace(self, **kw):
        """A copy with some axes swapped (re-validated)."""
        return dataclasses.replace(self, **kw)


# The registry and the dataclass must stay in lockstep: one field per axis.
_PK_FIELDS = tuple(f.name for f in dataclasses.fields(ProgramKey))
if _PK_FIELDS != tuple(ax.name for ax in PROGRAM_AXES):  # pragma: no cover
    raise AssertionError(
        f"ProgramKey fields {_PK_FIELDS} out of sync with PROGRAM_AXES "
        f"{tuple(ax.name for ax in PROGRAM_AXES)}")
