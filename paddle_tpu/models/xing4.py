"""Xing4.0-29B-A4B (``model_type: xing4_0``): GLM-4.7-Flash's attention and
expert FFN (multi-head latent attention in every layer; after
``first_k_dense_replace`` dense SwiGLU layers a mixture of routed experts
plus a shared one behind a sigmoid router) at other widths, with two
changes that ``models/glm4_moe_lite.py`` carries as facts of its
configuration:

- every sub-layer sits inside a manifold-constrained hyper-connection
  (``hc_mult`` = 4 residual streams mixed by a per-token Sinkhorn matrix of
  ``hc_sinkhorn_iters`` = 20 steps: ``ops/hyper_connection.py``);
- the positions are YaRN-scaled (``rope_scaling``), which also multiplies
  the softmax scale.

So this file is the published defaults and nothing else: the model, its
whole-sequence forward and its serving programs are the GLM family's own
(``GLM4_MOE_LITE_FAMILY``).  The multi-token-prediction module
(``num_nextn_predict_layers``) is not built, as for GLM.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from paddle_tpu.models.glm4_moe_lite import (
    Glm4MoeLiteConfig, Glm4MoeLiteForCausalLM,
)

__all__ = ["Xing4Config", "Xing4ForCausalLM"]


def _yarn():
    return {"type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_fast": 32,
            "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


@dataclass
class Xing4Config(Glm4MoeLiteConfig):
    """The published ``config.json`` keys (defaults: Xing4.0-29B-A4B)."""
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    routed_scaling_factor: float = 2.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e4
    max_position_embeddings: int = 262144
    rope_scaling: dict | None = field(default_factory=_yarn)
    hc_mult: int = 4

    # tiny preset used by the tests: 2 dense + 2 expert layers, four
    # streams, and a YaRN ramp that is active inside a 128-row table (lo = 0,
    # hi = 2: of the 4 rotary frequencies one is kept, one blended, two
    # divided by the factor)
    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                    moe_intermediate_size=48, num_hidden_layers=4,
                    first_k_dense_replace=2, num_attention_heads=4,
                    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
                    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=128,
                    rope_theta=100.0,
                    rope_scaling=dict(_yarn(), factor=8,
                                      original_max_position_embeddings=32),
                    dtype="float32")
        base.update(kw)
        return Xing4Config(**base)


class Xing4ForCausalLM(Glm4MoeLiteForCausalLM):
    """``Glm4MoeLiteForCausalLM`` under the published model's name."""
