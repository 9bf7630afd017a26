"""Llama-family causal LM — the flagship LLM config (BASELINE.json: Llama-2 /
ERNIE-Bot hybrid-parallel track; PaddleNLP's llama modeling is the reference
surface, built here TPU-first).

Design notes (TPU-first, not a translation):
  * bf16 weights by default — MXU-native; RMSNorm/softmax accumulate in fp32.
  * attention routes through F.scaled_dot_product_attention → Pallas flash
    kernel on TPU (paddle_tpu/ops/flash_attention.py); with ``sep_axis`` set,
    attention runs ring attention over that mesh axis (context parallelism the
    reference lacks, SURVEY.md §5.7).
  * ``llama_shardings``/``shard_llama`` lay parameters out Megatron-style over
    a ('dp', 'mp') mesh via NamedSharding; GSPMD propagates everything else —
    no hand-written collectives in the model body.
  * the forward's components run under ``jax.named_scope`` with the names of
    ``observability.trace.SCOPES`` — the same names the serving programs
    (models/llama_decode.py) carry — so a device trace of the compiled train
    step splits by component; JAX wraps them for the backward
    (``transpose(jvp(attn.core))``) and the recompute.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

import paddle_tpu.tensor.manipulation as M
from paddle_tpu.autograd.engine import apply
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.layer.common import Embedding, Linear
from paddle_tpu.nn.layer.container import LayerList
from paddle_tpu.nn.layer.layers import Layer
from paddle_tpu.nn.layer.norm import RMSNorm
from paddle_tpu.observability.trace import ATTN_RESIDUALS
from paddle_tpu.tensor.tensor import Tensor

__all__ = [
    "LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama_shardings",
    "shard_llama",
]


# what a recomputed layer's checkpoint keeps beside its input: the attention
# kernel's out and lse (LlamaConfig's comment on recompute_policy has the
# rule, its bytes and why it is not wider)
_RECOMPUTE_KEEPS = ATTN_RESIDUALS[3:]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    use_flash_attention: bool = True
    sep_axis: str | None = None  # mesh axis for ring-attention context parallel
    recompute: bool = False
    # Megatron-SP over the fleet "mp" axis: projections become Column/Row
    # SequenceParallelLinear (distributed/sep_utils.py) and the residual
    # stream between blocks stays sequence-sharded (requires
    # fleet.init(mp_degree>1) before model construction)
    sequence_parallel: bool = False
    # tokens per chunk for the LM loss: >0 computes the big-vocab
    # cross-entropy as a lax.scan over token chunks with per-chunk remat, so
    # the (B*L, vocab) fp32 logits tensor (≈4.2GB at batch 16/seq 2048/32k
    # vocab) never materializes — the usual TPU big-vocab loss shape; the
    # reference materializes full logits (fused_softmax_mask kernels help
    # softmax but not the memory)
    loss_chunk_size: int = 0
    # what a recomputed layer's jax.checkpoint keeps beside its input.
    # None (the default): the attention kernel's output and log-sum-exp
    # (observability.trace.ATTN_RESIDUALS: the flash custom VJPs name what
    # their backward reads), so the recompute inside the backward runs no
    # second attention forward.  Cost: 2*h + 4*heads bytes a token and
    # layer in bf16 (134 + 2 MB a layer at 16,384 tokens x hidden 4096 —
    # as much again as the layer input that full recompute keeps).  ONE
    # fixed rule, and not wider: also keeping q, then k and v (another
    # 2*h, then 4*h*kv_heads/heads bytes a token) was measured SLOWER on
    # the chip — at Mistral-7B's widths the step then passes the compiler's
    # memory budget and XLA re-runs MLP products on its own (PERF.md,
    # PR 32).  "full": the layer input only, everything run again — the
    # lean setting for a job at its memory limit.  "dots"/"dots_no_batch":
    # every matmul output (memory-hungry, small models only).
    recompute_policy: str | None = None
    # remat only the FIRST k decoder layers (None = all): un-remat layers
    # keep their intermediates (~14*h bytes/token/layer in bf16) and cost no
    # recompute FLOPs in backward — the HBM-for-FLOPs dial
    recompute_layers: int | None = None

    def __post_init__(self):
        # an unknown policy (or "named", which is gone) fails here, not at
        # the first forward
        from paddle_tpu.distributed.fleet.recompute import resolve_policy

        resolve_policy(self.recompute_policy)

    # tiny preset used by tests / dryrun
    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, max_position_embeddings=128)
        base.update(kw)
        return LlamaConfig(**base)


def _rope_cos_sin(seq_len, head_dim, theta, dtype):
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = jnp.outer(pos, inv)  # [L, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [L, D]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _apply_rope(q, k, theta, position_offset=0):
    """q/k: [B, L, H, D] jax arrays."""
    seq_len, head_dim = q.shape[1], q.shape[-1]
    cos, sin = _rope_cos_sin(position_offset + seq_len, head_dim, theta, q.dtype)
    cos = cos[position_offset:][None, :, None, :]
    sin = sin[position_offset:][None, :, None, :]

    def rot_half(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([-x2, x1], axis=-1)

    return q * cos + rot_half(q) * sin, k * cos + rot_half(k) * sin


def _sp_linears():
    from paddle_tpu.distributed.sep_utils import (
        ColumnSequenceParallelLinear, RowSequenceParallelLinear)

    col = lambda i, o: ColumnSequenceParallelLinear(
        i, o, has_bias=False, gather_output=False, seq_axis=1)
    row = lambda i, o: RowSequenceParallelLinear(
        i, o, has_bias=False, input_is_parallel=True, seq_axis=1)
    return col, row


def _chunked_lm_loss_fn(chunk_size):
    """Mean next-token cross-entropy computed chunk-by-chunk: the lm-head
    matmul + fp32 softmax run on ``chunk_size`` tokens at a time inside a
    ``lax.scan`` with per-chunk remat, so peak memory is one chunk's logits
    (the backward rescans and recomputes each chunk's matmul).  Shared
    implementation with BERT's masked-LM loss (ops/chunked_ce.py)."""
    from paddle_tpu.ops.chunked_ce import chunked_token_ce_fn

    return chunked_token_ce_fn(chunk_size, vh_weight=False, pad_label=-1)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h, nh, nkv = config.hidden_size, config.num_attention_heads, \
            config.num_key_value_heads
        self.head_dim = h // nh
        if config.sequence_parallel:
            col, row = _sp_linears()
            self.q_proj = col(h, nh * self.head_dim)
            self.k_proj = col(h, nkv * self.head_dim)
            self.v_proj = col(h, nkv * self.head_dim)
            self.o_proj = row(nh * self.head_dim, h)
        else:
            self.q_proj = Linear(h, nh * self.head_dim, bias_attr=False)
            self.k_proj = Linear(h, nkv * self.head_dim, bias_attr=False)
            self.v_proj = Linear(h, nkv * self.head_dim, bias_attr=False)
            self.o_proj = Linear(nh * self.head_dim, h, bias_attr=False)

    def forward(self, hidden_states, attn_mask=None, cache=None,
                position_offset=0):
        cfg = self.config
        b, l = hidden_states.shape[0], hidden_states.shape[1]
        nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
            self.head_dim
        with jax.named_scope("attn.qkv"):
            qp = self.q_proj(hidden_states)
            kp = self.k_proj(hidden_states)
            vp = self.v_proj(hidden_states)

        # NOTE: rope fused INTO the flash kernels exists
        # (ops/flash_attention.py::flash_attention_packed_rope, parity-
        # tested) but is NOT routed here: at the bench shapes it measured
        # ~11 ms/step SLOWER than the standalone rope kernel + flash —
        # the attention kernels are VPU-bound, so in-kernel rotation
        # extends their critical path by more than the bandwidth-bound
        # standalone pass costs (2x A/B, round 5, on an older stack).
        v = M.reshape(vp, [b, l, nkv, hd])

        def rope_fn(qa, ka):
            # Fast path: one Pallas pass rotates q and k straight off the
            # PACKED projections — the textbook split/negate/concat chain
            # materializes 5+ full-tensor XLA passes per call and forces
            # the layout copies the r5 profile priced at ~110 ms/step
            # (ops/fused_rope.py).
            from paddle_tpu.ops import fused_rope as _frope

            if _frope.available(qa.shape, ka.shape, nh, nkv):
                cos, sin = _rope_cos_sin(
                    position_offset + l, hd, cfg.rope_theta, qa.dtype)
                return _frope.fused_rope(
                    qa, ka, cos[position_offset:], sin[position_offset:],
                    nh, nkv)
            q4, k4 = _apply_rope(
                qa.reshape(b, l, nh, hd), ka.reshape(b, l, nkv, hd),
                cfg.rope_theta, position_offset)
            return q4.reshape(qa.shape), k4.reshape(ka.shape)

        with jax.named_scope("attn.rope"):
            qp, kp = apply("rope", rope_fn, qp, kp)
            q = M.reshape(qp, [b, l, nh, hd])
            k = M.reshape(kp, [b, l, nkv, hd])

        new_cache = None
        if cache is not None:
            pk, pv = cache
            if pk is not None:
                k = M.concat([pk, k], axis=1)
                v = M.concat([pv, v], axis=1)
            new_cache = (k, v)

        # GQA kv heads are consumed NATIVELY by every attention path: the
        # flash kernel blocks over kv heads (KV HBM traffic /G) and ring
        # attention rotates kv-head-sized shards (ICI bytes /G).
        with jax.named_scope("attn.core"):
            out = self._attend(q, k, v, attn_mask, l)
        with jax.named_scope("attn.out"):
            out = M.reshape(out, [b, l, nh * hd])
            out = self.o_proj(out)
        if cache is not None:
            return out, new_cache
        return out


    def _attend(self, q, k, v, attn_mask, l):
        cfg = self.config
        if cfg.sep_axis is not None:
            from paddle_tpu.distributed.auto_parallel.process_mesh import get_mesh
            from paddle_tpu.ops.ring_attention import ring_attention_sharded

            mesh = get_mesh().jax_mesh
            out = apply(
                "ring_attention",
                lambda qa, ka, va: ring_attention_sharded(
                    qa, ka, va, mesh, cfg.sep_axis, causal=True
                ), q, k, v,
            )
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask,
                is_causal=attn_mask is None and l > 1,
            )
        return out


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        if config.sequence_parallel:
            col, row = _sp_linears()
            self.gate_proj = col(h, i)
            self.up_proj = col(h, i)
            self.down_proj = row(i, h)
        else:
            self.gate_proj = Linear(h, i, bias_attr=False)
            self.up_proj = Linear(h, i, bias_attr=False)
            self.down_proj = Linear(i, h, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(
            config.hidden_size, config.rms_norm_eps
        )

    def forward(self, hidden_states, attn_mask=None, cache=None,
                position_offset=0):
        residual = hidden_states
        with jax.named_scope("norm"):
            h = self.input_layernorm(hidden_states)
        if cache is not None:
            h, new_cache = self.self_attn(h, attn_mask, cache, position_offset)
        else:
            h = self.self_attn(h, attn_mask, None, position_offset)
            new_cache = None
        h = residual + h
        residual = h
        with jax.named_scope("norm"):
            h = self.post_attention_layernorm(h)
        with jax.named_scope("mlp"):
            h = residual + self.mlp(h)
        if cache is not None:
            return h, new_cache
        return h


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = LayerList(
            [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)]
        )
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        if config.dtype != "float32":
            self.to(dtype=config.dtype)

    def forward(self, input_ids, attn_mask=None, caches=None,
                position_offset=0):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
        if self.config.sequence_parallel:
            if caches is not None:
                raise NotImplementedError(
                    "sequence_parallel training does not support KV caches; "
                    "build the model with sequence_parallel=False for decode"
                )
            from paddle_tpu.distributed.sep_utils import ScatterOp

            h = ScatterOp.apply(h, axis=1)  # residual stream seq-sharded
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            layer_fn = layer
            remat_this = self.config.recompute and caches is None and (
                self.config.recompute_layers is None
                or i < self.config.recompute_layers)
            if remat_this:
                from paddle_tpu.distributed.fleet.recompute import recompute

                policy = self.config.recompute_policy
                h = recompute(layer_fn, h, attn_mask,
                              policy=_RECOMPUTE_KEEPS if policy is None
                              else policy)
            elif caches is not None:
                h, c = layer_fn(h, attn_mask, caches[i], position_offset)
                new_caches.append(c)
            else:
                h = layer_fn(h, attn_mask)
        with jax.named_scope("norm"):
            h = self.norm(h)
        if self.config.sequence_parallel:
            from paddle_tpu.distributed.sep_utils import GatherOp

            h = GatherOp.apply(h, axis=1)  # full seq for the LM head
        if caches is not None:
            return h, new_caches
        return h


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = Linear(
                config.hidden_size, config.vocab_size, bias_attr=False
            )
            if config.dtype != "float32":
                self.lm_head.to(dtype=config.dtype)

    def serving_family(self):
        """The model seam of ``paddle_tpu.serving.ServingEngine``."""
        from paddle_tpu.models.llama_decode import LLAMA_FAMILY

        return LLAMA_FAMILY

    def forward(self, input_ids, labels=None, attn_mask=None):
        h = self.llama(input_ids, attn_mask)
        if labels is not None and self.config.loss_chunk_size > 0:
            w = (M.transpose(self.llama.embed_tokens.weight, [1, 0])
                 if self.config.tie_word_embeddings else self.lm_head.weight)
            return apply(
                "chunked_lm_loss",
                _chunked_lm_loss_fn(self.config.loss_chunk_size),
                h[:, :-1, :], labels[:, 1:], w,
            )
        with jax.named_scope("lm_head"):
            logits = self._head(h)
        if labels is None:
            return logits
        # next-token LM loss; logits in fp32 for a stable softmax
        with jax.named_scope("loss"):
            logits = logits.astype("float32")
            b, l, v = logits.shape
            shift_logits = M.reshape(logits[:, :-1, :], [b * (l - 1), v])
            shift_labels = M.reshape(labels[:, 1:], [b * (l - 1)])
            return F.cross_entropy(shift_logits, shift_labels)

    def generate(self, input_ids, max_new_tokens=32, eos_token_id=None):
        """Greedy decode with a per-layer KV cache (eager path)."""
        import jax.numpy as _jnp

        from paddle_tpu.autograd import engine as _engine

        with _engine.no_grad():
            caches = [(None, None)] * self.config.num_hidden_layers
            ids = input_ids
            h, caches = self.llama(ids, None, caches, 0)
            out_tokens = []
            cur_len = ids.shape[1]
            for _ in range(max_new_tokens):
                logits = self._head(h[:, -1:, :])
                nxt = Tensor(_jnp.argmax(logits.data, axis=-1).astype(_jnp.int64))
                out_tokens.append(nxt)
                if eos_token_id is not None and bool(
                    (nxt.data == eos_token_id).all()
                ):
                    break
                h, caches = self.llama(nxt, None, caches, cur_len)
                cur_len += 1
            return M.concat(out_tokens, axis=1)

    def _head(self, h):
        if self.config.tie_word_embeddings:
            return F.linear(
                h, M.transpose(self.llama.embed_tokens.weight, [1, 0])
            )
        return self.lm_head(h)


# ------------------------------------------------------------------ TP shardings
def llama_shardings(model: LlamaForCausalLM, mesh, dp_axis="dp", mp_axis="mp"):
    """name → placements map: Megatron layout over (dp, mp) — column-parallel
    q/k/v/gate/up (shard out-features), row-parallel o/down (shard in-features),
    vocab-parallel embedding + lm_head.  Replicated on every other axis."""
    from paddle_tpu.distributed.auto_parallel.placement_type import (
        Replicate, Shard,
    )

    has_mp = mp_axis in mesh.dim_names
    mp_idx = mesh.dim_names.index(mp_axis) if has_mp else None

    def place(shard_dim=None):
        pls = [Replicate() for _ in mesh.dim_names]
        if has_mp and shard_dim is not None:
            pls[mp_idx] = Shard(shard_dim)
        return pls

    out = {}
    for name, _ in model.named_parameters():
        if name.endswith(("q_proj.weight", "k_proj.weight", "v_proj.weight",
                          "gate_proj.weight", "up_proj.weight")):
            out[name] = place(1)  # weight [in, out]: shard out-features
        elif name.endswith(("o_proj.weight", "down_proj.weight")):
            out[name] = place(0)  # shard in-features
        elif name.endswith(("embed_tokens.weight", "lm_head.weight")):
            out[name] = place(0 if "embed" in name else 1)
        else:
            out[name] = place(None)  # norms: replicated
    return out


def shard_llama(model: LlamaForCausalLM, mesh, dp_axis="dp", mp_axis="mp"):
    """Apply llama_shardings in place via dist.shard_tensor (NamedSharding)."""
    from paddle_tpu.distributed.auto_parallel.api import shard_tensor

    placements = llama_shardings(model, mesh, dp_axis, mp_axis)
    for name, p in model.named_parameters():
        sharded = shard_tensor(p, mesh, placements[name],
                               stop_gradient=p.stop_gradient)
        p._data = sharded.data
    return model
