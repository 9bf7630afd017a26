"""The model seam of the serving engine: what ONE architecture provides and
``serving/engine.py`` reads, instead of importing an architecture's functions
by name.

A :class:`ServingFamily` is a plain record of functions and facts — no base
class to inherit, no registry.  A model names its family through
``model.serving_family()``; ``family_of`` is the engine's one lookup.

- ``decode_params(model, max_len) -> (params, cfg)``: the weights as a plain
  pytree (one device copy, cached on the model) and the hashable statics of
  the compiled programs.
- ``rows_leaves(cfg) -> RowsLeaves``: what the family's ROWS leaves are —
  the cache leaves that hold one row a position and that a slot's length
  makes harmless: how many lead a layer's cache tuple (2: a K and a V
  leaf; 1: one latent row), the shape of one position's row in each, and
  the query heads that read them.  It sizes the cache manager, the byte
  gauges and the mesh check.
- ``init_layer_cache(cfg, batch, max_len, kv_dtype) -> tuple``: ONE layer's
  cache leaves: the rows leaves first (``[B, Lmax, *row]``; for (k, v)
  ``ops.decode_attention.init_kv_cache``), then what ``state_leaves``
  describes.
- the compiled programs, all with ``models/llama_decode.py``'s signatures and
  names: ``decode_steps``, ``prefill_chunk`` (every family), ``spec_step``,
  ``spec_draft_step`` (``None`` where the family has none).
- ``quantize_weights`` (``None``: no int8 weights) and ``tp_rules`` (the
  partition rules of ``serving/sharding.py``; ``None``: no mesh).
- ``routed_experts(params) -> int`` (``None``: no routed experts): a family
  whose programs hand back, beside the tokens, the experts that served
  each live row — ``decode_steps`` a fourth result ``int8 [B, n_steps,
  L_moe, k]``, ``prefill_chunk`` a sixth ``int8 [P, L_moe, k]``, ``-1`` for
  a row that is not live.  The engine drains them with the tokens, feeds
  its expert counters and appends them to ``Request.routes``.
- ``check_options(options)``: raises ``ValueError`` for an engine option the
  family cannot serve, naming the missing piece — construction-time, never
  a silently wrong stream.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

__all__ = ["RowsLeaves", "ServingFamily", "StateLeaf", "family_of"]


@dataclasses.dataclass(frozen=True)
class StateLeaf:
    """One per-slot cache leaf that is NOT K/V rows: a length does not make
    it harmless, so the family's prefill-chunk program resets it for the
    slot inside a request's first chunk (offset 0) and its decode program
    leaves parked slots' entries untouched."""
    name: str
    index: int          # position in a layer's cache tuple
    reset: str          # how a new tenant of a slot gets a clean one


@dataclasses.dataclass(frozen=True)
class RowsLeaves:
    """A layer's rows leaves: ``count`` leaves ``[B, Lmax, *row]`` lead its
    cache tuple; ``query_heads`` attention heads read them."""
    count: int          # 2: (k, v); 1: one latent row
    row: tuple          # (kv_heads, row_width) of one position
    query_heads: int


@dataclasses.dataclass(frozen=True)
class ServingFamily:
    name: str
    decode_params: Callable
    rows_leaves: Callable
    init_layer_cache: Callable
    decode_steps: Callable
    prefill_chunk: Callable
    spec_step: Optional[Callable] = None
    spec_draft_step: Optional[Callable] = None
    quantize_weights: Optional[Callable] = None
    tp_rules: Optional[Callable] = None
    routed_experts: Optional[Callable] = None
    state_leaves: tuple = ()
    check_options: Callable = lambda options: None


def family_of(model):
    """The serving family of ``model`` (``model.serving_family()``)."""
    get = getattr(model, "serving_family", None)
    if get is None:
        raise TypeError(
            f"{type(model).__name__} names no serving family: a model the "
            "engine serves provides serving_family() "
            "(models/serving_family.py)")
    return get()
