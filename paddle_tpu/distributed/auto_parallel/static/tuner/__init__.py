"""Auto-parallel planner ("tuner-lite").

Reference: python/paddle/distributed/auto_parallel/static/tuner/
(parallel_tuner.py — search over process meshes; rule_based_tuner.py —
pattern-matched plans; config.py/cluster.py — the cluster description).

TPU-native inversion: the reference tunes a serialized program by
partitioning ops across a GPU cluster description and profiling trials.
On TPU the mesh IS the plan — GSPMD handles op partitioning once the
(dp, mp, pp, sep) degrees are chosen — so the planner's job reduces to
choosing the degrees + remat policy.  This module enumerates every legal
mesh for a transformer ModelDesc, scores each with an analytic
compute/HBM/ICI model (calibratable against XLA cost analysis via
``Engine.cost``), drops infeasible ones on memory, and returns the argmin.

The scoring model is the public roofline recipe (jax-ml.github.io/
scaling-book): per-step time = max(compute, HBM) + exposed collectives,
with Megatron-TP all-reduces, ZeRO/DP gradient reduction, pipeline bubble,
and ring-attention (sep) rotation costed against ICI bandwidth.
"""
from __future__ import annotations

import dataclasses
import math

__all__ = ["DeviceSpec", "ModelDesc", "ParallelPlan", "Planner"]


# chip generation -> (bf16 peak TFLOP/s, HBM GiB, HBM GB/s, ICI GB/s per link)
_CHIPS = {
    "TPU v4": (275.0, 32, 1200.0, 100.0),
    "TPU v5 lite": (197.0, 16, 820.0, 100.0),
    "TPU v5e": (197.0, 16, 820.0, 100.0),
    "TPU v5p": (459.0, 95, 2765.0, 200.0),
    "TPU v6 lite": (918.0, 32, 1640.0, 200.0),
    "TPU v6e": (918.0, 32, 1640.0, 200.0),
}


@dataclasses.dataclass
class DeviceSpec:
    """The cluster description (reference auto_parallel/static/cluster.py,
    reduced to what a TPU slice needs: one homogeneous chip type + fabric)."""

    peak_tflops: float = 197.0
    hbm_gib: float = 16.0
    hbm_gbps: float = 820.0
    ici_gbps: float = 100.0
    dcn_gbps: float = 6.25  # per-host DCN when a mesh axis leaves the slice
    mxu_efficiency: float = 0.55  # calibrate with Engine.cost / measured MFU
    # latency floor per collective (dispatch + first-hop): decides the plan
    # for small models where every bandwidth term is sub-microsecond
    coll_latency_s: float = 5e-6

    @classmethod
    def detect(cls):
        """The spec of the chip jax runs on.  On the CPU platform (tests,
        planning ahead of a deployment) the analytic planner gets the
        field defaults — a v5e, stated here, not guessed; an accelerator
        whose ``device_kind`` is not in ``_CHIPS`` is an error."""
        import jax

        dev = jax.devices()[0]
        if dev.platform == "cpu":
            return cls()
        for prefix, (tf, gib, hbm, ici) in _CHIPS.items():
            if dev.device_kind.startswith(prefix):
                return cls(peak_tflops=tf, hbm_gib=gib, hbm_gbps=hbm,
                           ici_gbps=ici)
        raise ValueError(
            f"DeviceSpec.detect: no spec on record for device_kind "
            f"{dev.device_kind!r} (platform {dev.platform!r}); known: "
            f"{sorted(_CHIPS)} — pass an explicit DeviceSpec")


@dataclasses.dataclass
class ModelDesc:
    """Transformer shape for the analytic cost model."""

    n_params: int
    n_layers: int
    hidden: int
    heads: int
    kv_heads: int
    intermediate: int
    vocab: int
    batch: int
    seq: int
    dtype_bytes: int = 2  # bf16 weights/activations

    @classmethod
    def from_model(cls, model, batch, seq):
        """Best-effort extraction: explicit config attrs (LlamaConfig-style)
        win; otherwise fall back to parameter statistics."""
        import numpy as np

        n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
        cfg = getattr(model, "config", None)
        get = lambda *names: next(
            (int(getattr(cfg, n)) for n in names if hasattr(cfg, n)), None)
        if cfg is not None and get("hidden_size") is not None:
            hidden = get("hidden_size")
            heads = get("num_attention_heads") or max(1, hidden // 128)
            return cls(
                n_params=n_params,
                n_layers=get("num_hidden_layers", "num_layers") or 1,
                hidden=hidden,
                heads=heads,
                kv_heads=get("num_key_value_heads") or heads,
                intermediate=get("intermediate_size") or 4 * hidden,
                vocab=get("vocab_size") or 32000,
                batch=batch, seq=seq,
            )
        # fallback: square-ish transformer guess from parameter count
        hidden = 1 << max(8, int(math.log2(max(n_params, 1) ** (1 / 3))))
        return cls(n_params=n_params, n_layers=1, hidden=hidden,
                   heads=max(1, hidden // 128), kv_heads=max(1, hidden // 128),
                   intermediate=4 * hidden, vocab=32000,
                   batch=batch, seq=seq)


@dataclasses.dataclass
class ParallelPlan:
    dp: int
    mp: int
    pp: int
    sep: int
    recompute: bool
    micro_batches: int
    t_step_s: float
    breakdown: dict
    feasible: bool

    @property
    def degrees(self):
        return {"dp_degree": self.dp, "mp_degree": self.mp,
                "pp_degree": self.pp, "sep_degree": self.sep}


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


class Planner:
    """Enumerate legal (dp, mp, pp, sep) meshes + remat policies and rank
    them by the analytic step-time model.  ``plan()`` returns every feasible
    candidate sorted best-first; ``tune()`` the argmin."""

    def __init__(self, desc: ModelDesc, n_devices: int,
                 device: DeviceSpec | None = None):
        self.desc = desc
        self.n_devices = int(n_devices)
        self.device = device or DeviceSpec.detect()

    # ------------------------------------------------------------ enumerate
    def candidates(self):
        d = self.desc
        out = []
        for mp in _divisors(self.n_devices):
            if d.hidden % mp or d.heads % mp or d.intermediate % mp:
                continue
            if d.kv_heads % mp and mp % d.kv_heads:
                continue  # kv heads must tile (or replicate) evenly
            rest = self.n_devices // mp
            for pp in _divisors(rest):
                if pp > 1 and d.n_layers % pp:
                    continue
                rest2 = rest // pp
                for sep in _divisors(rest2):
                    if d.seq % sep:
                        continue
                    dp = rest2 // sep
                    if d.batch % (dp or 1):
                        continue
                    for recompute in (False, True):
                        out.append((dp, mp, pp, sep, recompute))
        return out

    # ---------------------------------------------------------------- score
    def score(self, dp, mp, pp, sep, recompute):
        d, dev = self.desc, self.device
        tokens = d.batch * d.seq
        GB = 1e9

        # ---- compute: model matmul FLOPs + causal attention FLOPs
        flops = (6 * d.n_params + 6 * d.n_layers * d.hidden * d.seq) * tokens
        if recompute:
            flops *= 4 / 3  # forward replayed in backward
        t_compute = flops / (self.n_devices * dev.peak_tflops * 1e12
                             * dev.mxu_efficiency)

        # ---- pipeline bubble (1F1B): idle fraction (pp-1)/(m+pp-1)
        micro = max(dp * 2, 2 * pp) if pp > 1 else 1
        bubble = (pp - 1) / (micro + pp - 1) if pp > 1 else 0.0
        t_bubble = t_compute * bubble

        # ---- Megatron-TP: 4 all-reduces of the activation block per layer
        # per step (2 fwd + 2 bwd); all-reduce cost 2(n-1)/n * bytes / bw
        act_bytes = tokens * d.hidden * d.dtype_bytes / max(dp * sep, 1)
        lat = dev.coll_latency_s
        t_tp = 0.0
        if mp > 1:
            per_ar = (2 * (mp - 1) / mp * act_bytes / (dev.ici_gbps * GB)
                      + lat)
            t_tp = d.n_layers * 4 * per_ar

        # ---- DP gradient all-reduce (overlaps backward: half exposed)
        t_dp = 0.0
        if dp > 1:
            grad_bytes = d.n_params * d.dtype_bytes / max(mp * pp, 1)
            t_dp = (0.5 * 2 * (dp - 1) / dp * grad_bytes
                    / (dev.ici_gbps * GB) + lat)

        # ---- sep (ring attention): K/V shards rotate sep-1 times, fwd+bwd
        t_sep = 0.0
        if sep > 1:
            kv_bytes = (2 * tokens * d.hidden * (d.kv_heads / d.heads)
                        * d.dtype_bytes / (dp * sep))
            t_sep = (3 * (sep - 1)
                     * (kv_bytes / (dev.ici_gbps * GB) + lat * d.n_layers))

        # ---- memory per device (bf16 weights + fp32 master + int8/bf16
        # moments + bf16 grads; activations by remat policy)
        shard = max(mp * pp, 1)
        p_bytes = d.n_params / shard * (2 + 4 + 1 + 2 + 2)
        act_per_layer = (tokens * (10 * d.hidden + 2 * d.intermediate)
                         * d.dtype_bytes / max(dp * mp * sep, 1))
        layers_here = d.n_layers / max(pp, 1)
        if recompute:
            act = layers_here * tokens * d.hidden * d.dtype_bytes \
                / max(dp * sep, 1) + act_per_layer  # boundaries + one live
        else:
            act = layers_here * act_per_layer
        mem = p_bytes + act
        feasible = mem < dev.hbm_gib * (1 << 30) * 0.92

        t = t_compute + t_bubble + t_tp + t_dp + t_sep
        return ParallelPlan(
            dp=dp, mp=mp, pp=pp, sep=sep, recompute=recompute,
            micro_batches=micro, t_step_s=t,
            breakdown={
                "t_compute": t_compute, "t_bubble": t_bubble, "t_tp": t_tp,
                "t_dp": t_dp, "t_sep": t_sep, "mem_gib": mem / (1 << 30),
            },
            feasible=feasible,
        )

    # ----------------------------------------------------------------- tune
    def plan(self):
        plans = [self.score(*c) for c in self.candidates()]
        feas = [p for p in plans if p.feasible]
        pool = feas or plans  # nothing fits: still return the least-bad
        return sorted(pool, key=lambda p: p.t_step_s)

    def tune(self):
        ranked = self.plan()
        if not ranked:
            raise ValueError(
                f"no legal mesh for {self.n_devices} devices and "
                f"model {self.desc}")
        return ranked[0]
