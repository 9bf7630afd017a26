"""Chipless compiles for a described TPU v5e: every Pallas kernel on the
serving and training main paths, at the real widths of the ``chip_smoke.py``
model (hidden 2048, 16 heads / 4 kv heads, head dim 128, Lmax 2048, batch 8
serving / 16 training, bf16 and int8+f16-scale caches) and, further down, of
the benchmark's configurations (``benchmark/configs/``).

Interpret-mode parity suites run the kernels' LOGIC on the CPU; they cannot
see what the TPU lowering refuses (block shapes off the (8, 128) tiling, f16
vector loads on v5e, DMA windows on a padded minor dim, int64 indices under
x64, scoped-VMEM overflow).  These tests hand the installed TPU compiler the
shapes — no chip, nothing runs — so each later PR keeps the kernels
compilable for free.  A compile that passes is not a chip run:
``chip_smoke.py`` is.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and every xdist worker imports
every test file), in this ONE file, in the test's own process.
"""
import collections
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu  # noqa: F401  (x64 mode: the index-dtype trap the kernels guard)
from paddle_tpu.ops.decode_attention import init_kv_cache, init_kv_pool

B, H, HKV, D, LMAX, C = 8, 16, 4, 128, 2048, 128
G = H // HKV
T_PREFILL = 256
TRAIN_B, TRAIN_L = 16, 2048


@pytest.fixture(scope="module")
def one_chip():
    """SingleDeviceSharding on chip 0 of a described v5e 2x2.  For the
    module, the persistent compilation cache is off (an entry written for
    a described chip cannot be read back without one — the next run would
    warn and recompile anyway) and the matmul precision is JAX's default,
    as on the chip: conftest's "highest" (for the float64-referenced
    numeric tests) would ask Mosaic for fp32 passes over bf16 operands,
    which it refuses and no product path requests."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here / library held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_default_matmul_precision)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was[0])
        jax.config.update("jax_default_matmul_precision", was[1])
        cc.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, *args, **jit_kw):
    """Compile for the described chip; the kernel must be IN the program."""
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _cache(kind, sharding):
    """Abstract (k, v) caches of one layer at serving widths."""
    dtype = "int8" if kind.endswith("int8") else jnp.bfloat16
    if kind.startswith("paged"):
        make = functools.partial(init_kv_pool, B * LMAX // C, C, HKV, D, dtype)
    else:
        make = functools.partial(init_kv_cache, B, LMAX, HKV, D, dtype)
    return _shapes(jax.eval_shape(make), sharding)


@pytest.mark.parametrize("t", [1, 5], ids=["step", "verify5"])
@pytest.mark.parametrize("kind", ["dense-bf16", "paged-bf16", "paged-int8"])
def test_fused_decode_kernel_compiles(one_chip, kind, t):
    """``attn_impl="pallas"``: the decode step (T=1) and the speculative
    verify forward (T=k+1) over dense, paged and paged-int8 caches."""
    from paddle_tpu.ops.paged_attention_pallas import fused_decode_attention

    k, v = _cache(kind, one_chip)
    qg = jax.ShapeDtypeStruct((B, HKV, G, t, D), jnp.float32,
                              sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    args = [qg, k, v, lengths]
    if kind.startswith("paged"):
        args.append(jax.ShapeDtypeStruct((B, LMAX // C), jnp.int32,
                                         sharding=one_chip))

    def run(qg, k, v, lengths, table=None):
        return fused_decode_attention(qg, k, v, lengths, D ** -0.5, C,
                                      block_table=table, interpret=False)

    _compile(run, *args)


@pytest.mark.parametrize(
    "kind", ["paged-bf16", "paged-int8", "dense-bf16", "dense-int8"])
def test_fused_prefill_kernel_compiles(one_chip, kind):
    """``prefill_impl="pallas"`` at the engine's default 256-token chunk:
    attention + (quantize-on-)append, pool leaves aliased in place."""
    from paddle_tpu.ops.prefill_attention_pallas import (
        fused_prefill_attention)

    k, v = _cache(kind, one_chip)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    q = sds((1, T_PREFILL, H, D), jnp.bfloat16)
    kn = sds((1, T_PREFILL, HKV, D), jnp.bfloat16)
    scalar = sds((), jnp.int32)
    args = [q, kn, kn, k, v, scalar, scalar]
    if kind.startswith("paged"):
        args.append(sds((1, LMAX // C), jnp.int32))

    def run(q, kn, vn, k, v, slot, offset, table=None):
        return fused_prefill_attention(q, kn, vn, k, v, slot, offset,
                                       D ** -0.5, C, block_table=table,
                                       interpret=False)

    compiled = _compile(run, *args, donate_argnums=(3, 4))
    # the append is in place: every pool leaf is aliased input -> output
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves((k, v)))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


def test_flash_attention_fwd_bwd_compiles(one_chip):
    """The training attention: causal GQA flash fwd + bwd at the smoke
    batch (B16 x L2048 x H16/Hkv4 x D128, bf16)."""
    from paddle_tpu.ops.flash_attention import flash_attention_blhd

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    q = sds((TRAIN_B, TRAIN_L, H, D), jnp.bfloat16)
    kv = sds((TRAIN_B, TRAIN_L, HKV, D), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention_blhd(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                        q, kv, kv)
    text = compiled.as_text()
    assert "_flash_fwd_pallas" in text and "_flash_bwd_pallas" in text


def test_fused_rope_fwd_bwd_compiles(one_chip):
    from paddle_tpu.ops.fused_rope import fused_rope

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    q = sds((TRAIN_B, TRAIN_L, H * D), jnp.bfloat16)
    k = sds((TRAIN_B, TRAIN_L, HKV * D), jnp.bfloat16)
    table = sds((TRAIN_L, D), jnp.float32)

    def loss(q, k, cos, sin):
        oq, ok = fused_rope(q, k, cos, sin, H, HKV, False)
        return jnp.sum(oq.astype(jnp.float32)) \
            + jnp.sum(ok.astype(jnp.float32))

    _compile(jax.value_and_grad(loss, argnums=(0, 1)), q, k, table, table)


def test_fused_adamw_q8_compiles(one_chip):
    """The int8-moment AdamW update on the llama MLP leaf [2048, 5632]
    (bf16 parameter with an f32 master, int8 first moment + f32 block
    scales, bf16 second moment)."""
    from paddle_tpu.ops.fused_adamw import fused_adamw_q8

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    shape = (2048, 5632)
    n = shape[0] * shape[1]
    args = (sds(shape, jnp.float32), sds(shape, jnp.bfloat16),
            sds(shape, jnp.int8), sds((n // 256,), jnp.float32),
            sds(shape, jnp.bfloat16), sds((16,), jnp.float32))
    _compile(functools.partial(fused_adamw_q8, interpret=False), *args)


# Mistral-7B-v0.3's widths (the benchmark's serving cells), 2 of its layers
M_HID, M_INTER, M_NH, M_NKV, M_VOCAB, M_LAYERS = 4096, 14336, 32, 8, 32768, 2
_COPY = re.compile(r"= \w+\[(\d+),(\d+)\]\S* (?:copy|transpose)\(")


def _weight_copies(text):
    """The ``copy`` / ``transpose`` instructions of a compiled program whose
    result is a matrix of 2^22 elements or more: a decode weight (``wk``
    [4096, 1024] is the smallest) brought into another order."""
    return [m.group(0) for m in _COPY.finditer(text)
            if int(m.group(1)) * int(m.group(2)) >= 2 ** 22]


def _mistral_program(one_chip, program, batch, lmax, rows=T_PREFILL):
    """The decode-steps or prefill-chunk program of the Mistral serving
    cells (2 layers, abstract operands), lowered for the described chip;
    ``rows``: the prompt rows of a prefill run."""
    from paddle_tpu.models import llama_decode as ld

    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    bf16 = functools.partial(sds, dtype=jnp.bfloat16)
    i32 = functools.partial(sds, dtype=jnp.int32)
    layer = {"ln1": bf16((M_HID,)), "ln2": bf16((M_HID,)),
             "wq": bf16((M_HID, M_NH * D)), "wk": bf16((M_HID, M_NKV * D)),
             "wv": bf16((M_HID, M_NKV * D)), "wo": bf16((M_NH * D, M_HID)),
             "gate": bf16((M_HID, M_INTER)), "up": bf16((M_HID, M_INTER)),
             "down": bf16((M_INTER, M_HID))}
    params = {"embed": bf16((M_VOCAB, M_HID)), "norm": bf16((M_HID,)),
              "lm_head": bf16((M_HID, M_VOCAB)),
              "layers": [dict(layer) for _ in range(M_LAYERS)],
              "_rope": (bf16((lmax, D)), bf16((lmax, D)))}
    caches = [(bf16((batch, lmax, M_NKV, D)), bf16((batch, lmax, M_NKV, D)))
              for _ in range(M_LAYERS)]
    cfg = (M_NH, M_NKV, D, 1e-5)
    if program == "decode_steps":
        return ld.serving_decode_steps.__wrapped__.lower(
            params, cfg, i32((batch,)), caches, i32((batch,)), n_steps=1,
            chunk_size=256)
    return ld.serving_prefill_chunk.__wrapped__.lower(
        params, cfg, i32((1, rows)), i32(()), i32((1,)), caches,
        i32(()), chunk_size=256)


@pytest.mark.parametrize("batch,lmax", [(32, 2048), (16, 4096)],
                         ids=["32x2048", "16x4096"])
@pytest.mark.parametrize("program", ["decode_steps", "prefill_chunk"])
def test_serving_programs_read_weights_as_stored(one_chip, program, batch,
                                                 lmax):
    """The decode-steps and prefill-chunk programs at both serving cells'
    geometries consume every weight in the order it is stored.  A reshape
    to ``[.., heads, head_dim]`` directly behind the Q/K/V dots made the
    compiler copy ``wq``, ``wk`` and ``wv`` of every layer into the other
    order on every run (2.6 ms of a 16.5 ms decode step: PERF.md, PR 27).
    The K/V cache chunks' own re-layout inside ``attn.core.chunks``
    (rank 4) is not a weight's and is not counted here."""
    lowered = _mistral_program(one_chip, program, batch, lmax)
    assert _weight_copies(lowered.compile().as_text()) == []


# Falcon-H1-34B's widths (the benchmark's falconh1_chat_short cell), 2 of
# its blocks at the cell's geometry
def _falcon_program(one_chip, program, batch=64, lmax=1024, layers=2):
    """The same two programs of the Falcon-H1 cell; returns ``(lowered,
    state leaf's shape)``."""
    from paddle_tpu.models import falcon_h1_decode as fd
    from paddle_tpu.models.falcon_h1 import FalconH1Config, statics_of

    c = FalconH1Config(num_hidden_layers=layers)
    cfg = statics_of(c)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    bf16 = functools.partial(sds, dtype=jnp.bfloat16)
    i32 = functools.partial(sds, dtype=jnp.int32)
    h, inter = c.hidden_size, c.intermediate_size
    qd, kvd = cfg.heads * D, cfg.kv_heads * D
    layer = {"ln1": bf16((h,)), "ln2": bf16((h,)), "wq": bf16((h, qd)),
             "wk": bf16((h, kvd)), "wv": bf16((h, kvd)), "wo": bf16((qd, h)),
             "w_in": bf16((h, sum(cfg.segments))),
             "conv_w": bf16((cfg.d_conv, cfg.conv_channels)),
             "conv_b": bf16((cfg.conv_channels,)),
             "dt_bias": bf16((cfg.ssm_heads,)),
             "a_log": bf16((cfg.ssm_heads,)), "d": bf16((cfg.ssm_heads,)),
             "norm_w": bf16((cfg.d_ssm,)), "w_out": bf16((cfg.d_ssm, h)),
             "gate": bf16((h, inter)), "up": bf16((h, inter)),
             "down": bf16((inter, h))}
    params = {"embed": bf16((c.vocab_size, h)), "norm": bf16((h,)),
              "lm_head": bf16((h, c.vocab_size)),
              "layers": [dict(layer) for _ in range(layers)],
              "_rope": (bf16((lmax, D)), bf16((lmax, D)))}
    state = (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state)
    caches = [(bf16((batch, lmax, cfg.kv_heads, D)),
               bf16((batch, lmax, cfg.kv_heads, D)),
               sds(state, jnp.float32),
               bf16((batch, cfg.d_conv - 1, cfg.conv_channels)))
              for _ in range(layers)]
    if program == "decode_steps":
        return fd.serving_decode_steps.__wrapped__.lower(
            params, cfg, i32((batch,)), caches, i32((batch,)), n_steps=1,
            chunk_size=256), state
    return fd.serving_prefill_chunk.__wrapped__.lower(
        params, cfg, i32((1, T_PREFILL)), i32(()), i32((1,)), caches,
        i32(()), chunk_size=256), state


@pytest.mark.parametrize("program", ["decode_steps", "prefill_chunk"])
def test_state_space_serving_programs_compile_in_place(one_chip, program,
                                                       monkeypatch):
    """The two Falcon-H1 serving programs compile for the chip at published
    widths, read every weight in the order it is stored (the Q/K/V barrier
    of ``falcon_h1.attn_qkv``), and update the float32 recurrent state
    ``[64, 32, 128, 256]`` in place: no copy of a state leaf (4 of them
    would be the cell's 1.6 GB again).  The decode update is the Pallas
    kernel under ``ssm.state_update`` (its ``interpret`` rule steered to
    the TPU branch for the trace), and no select over a whole state leaf
    is left of the update that read and wrote every slot."""
    if program == "decode_steps":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered, state = _falcon_program(one_chip, program)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert _weight_copies(text) == []
    leaf = "f32[%d,%d,%d,%d]" % state
    assert [ln for ln in text.split("\n")
            if leaf in ln.split("=")[-1][:60] and " copy(" in ln] == []
    # donated caches are updated in place: the temporaries stay far under
    # one state leaf (268 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 128 * 2 ** 20
    if program == "decode_steps":
        kernels = [ln for ln in text.split("\n")
                   if "custom_call_target=\"tpu_custom_call\"" in ln
                   and "ssm_state_update" in ln]
        assert len(kernels) == 2                      # one a layer
        assert all("ssm.state_update" in _op_name(ln) for ln in kernels)
        assert [ln for ln in text.split("\n")
                if (m := re.search(r"= f32\[([\d,]+)\]\S* select\(", ln))
                and math.prod(map(int, m.group(1).split(",")))
                == math.prod(state)] == []


def _op_name(line):
    m = re.search(r'op_name="([^"]*)"', line)
    return m.group(1) if m else ""


# GLM-4.7-Flash's widths (the benchmark's glm47flash_code_steady cell): the
# dense layer and one expert layer at the cell's geometry
def _glm_program(one_chip, program, monkeypatch, batch=64, lmax=4608,
                 rows=T_PREFILL, config=None):
    """The same two programs of the GLM-4.7-Flash cell (or, with
    ``config``, of another model of its family: a dense and an expert
    layer); returns ``(lowered, latent leaf's shape, experts' shape)``.
    The grouped product's ``interpret`` rule is steered to its TPU branch
    for the trace."""
    from paddle_tpu.models import glm4_moe_lite_decode as gd
    from paddle_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                                 statics_of)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    c = config or Glm4MoeLiteConfig(num_hidden_layers=2)
    cfg = statics_of(c)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    bf16 = functools.partial(sds, dtype=jnp.bfloat16)
    i32 = functools.partial(sds, dtype=jnp.int32)
    h, heads = c.hidden_size, c.num_attention_heads
    e, f = c.n_routed_experts, c.moe_intermediate_size
    attn = {"ln1": bf16((h,)), "ln2": bf16((h,)),
            "w_dq": bf16((h, c.q_lora_rank)),
            "q_norm": bf16((c.q_lora_rank,)),
            "w_uq": bf16((c.q_lora_rank, heads * (cfg.nope + cfg.rope))),
            "w_dkv": bf16((h, cfg.row)), "kv_norm": bf16((cfg.kv_rank,)),
            "w_uk": bf16((heads, cfg.nope, cfg.kv_rank)),
            "w_uv": bf16((heads, cfg.kv_rank, cfg.v_dim)),
            "wo": bf16((heads * cfg.v_dim, h))}
    if cfg.hc > 1:
        cols = cfg.hc * cfg.hc + 2 * cfg.hc
        for which in (1, 2):
            attn.update({
                f"hc{which}_phi": sds((cfg.hc * h, cols), jnp.float32),
                f"hc{which}_b": sds((cols,), jnp.float32),
                f"hc{which}_alpha": sds((3,), jnp.float32)})
    dense = dict(attn, gate=bf16((h, c.intermediate_size)),
                 up=bf16((h, c.intermediate_size)),
                 down=bf16((c.intermediate_size, h)))
    experts = (e, h, f)
    moe = dict(attn, router=bf16((h, e)), router_bias=sds((e,), jnp.float32),
               e_gate=bf16(experts), e_up=bf16(experts),
               e_down=bf16((e, f, h)), s_gate=bf16((h, f)),
               s_up=bf16((h, f)), s_down=bf16((f, h)))
    params = {"embed": bf16((c.vocab_size, h)), "norm": bf16((h,)),
              "lm_head": bf16((h, c.vocab_size)), "layers": [dense, moe],
              "_rope": (bf16((lmax, cfg.rope)), bf16((lmax, cfg.rope)))}
    leaf = (batch, lmax, cfg.row_stored)
    caches = [(bf16(leaf),) for _ in range(2)]
    if program == "decode_steps":
        return gd.serving_decode_steps.__wrapped__.lower(
            params, cfg, i32((batch,)), caches, i32((batch,)), n_steps=1,
            chunk_size=256), leaf, experts
    return gd.serving_prefill_chunk.__wrapped__.lower(
        params, cfg, i32((1, rows)), i32(()), i32((1,)), caches,
        i32(()), chunk_size=256), leaf, experts


def _chunk_loops(text):
    """``[(the while's line, its body's lines)]`` of the cache-chunk loops
    of a compiled text."""
    comps = {name: lines for name, _, lines in _computations(text)}
    return [(ln, comps[re.search(r"body=(%[\w.\-]+)", ln).group(1)])
            for ln in text.split("\n") if " while(" in ln
            and re.search(r'op_name="[^"]*attn\.core\.chunks/while"', ln)]


def _one_gather_a_layer(text, leaf, layers=2):
    """A trip of the decode program's per-slot read of ONE latent rows leaf
    ``[B, Lmax, R]`` (bfloat16: a tile packs 16 rows) is exactly ONE
    ``gather`` a layer, of 8 slots' windows of 256 / 16 groups of the
    ``[B * Lmax / 16, 16, R]`` view; that view is a ``bitcast`` of the leaf
    (its physical order) and nothing else of its shape is made; no
    ``dynamic-update-slice`` assembles an ``[8, 256, 1, R]`` block slot by
    slot and the chunk loop's body holds no loop of its own (what the TPU
    compiler makes of windows of the flat ``[B * Lmax, 1, R]`` view: ~560
    window copies a GLM decode run, PERF.md PR 33 and PR 36)."""
    b, lmax, r = leaf
    view = r"bf16\[%d,16,%d\]" % (b * lmax // 16, r)
    gathers = [ln for ln in text.split("\n") if re.search(
        r"= bf16\[8,16,16,%d\]\S* gather\(" % r, ln)]
    assert len(gathers) == layers, gathers
    assert all("slice_sizes={16,16,%d}" % r in ln for ln in gathers)
    made = [op for op in re.findall(
        r"= %s\S* (\w[\w\-]*)\(" % view, text) if op != "parameter"]
    assert made == ["bitcast"] * layers, made
    # each gather reads that view: directly, or as the parameter of the
    # fusion that holds it
    for name, fused, lines in _computations(text):
        for ln in lines:
            if ln in gathers:
                operand = re.search(r"gather\((%[\w.\-]+),", ln).group(1)
                shape = [x for x in lines if re.match(
                    r"\s*(?:ROOT )?%s = " % re.escape(operand), x)]
                assert shape and re.search(r"= %s" % view, shape[0]), shape
    assert "bf16[%d,1,%d]" % (b * lmax, r) not in text
    assert not re.search(
        r"dynamic-update-slice\S* = bf16\[8,256,1,%d\]" % r, text)
    loops = _chunk_loops(text)
    assert len(loops) == layers
    for _, body in loops:
        assert not any(" while(" in ln for ln in body)


@pytest.mark.parametrize("program", ["decode_steps", "prefill_chunk"])
def test_expert_latent_serving_programs_compile_in_place(one_chip, program,
                                                         monkeypatch):
    """The two GLM-4.7-Flash serving programs compile for the chip at
    published widths (64 experts of [2048, 1536], 64 slots x 4,608 latent
    rows of 576): the grouped expert products are IN the program (three a
    layer), no weight — 2-D or a stacked expert tensor — and no latent
    cache leaf is copied into another order, and a chunk trip gathers the
    latent leaf ONCE a layer (the row is key and value both: not one
    gather for keys and one for values, as a (k, v) family's two) — as a
    ``gather`` the compiler keeps, not the loop of window copies it made
    of the flat view's (``_one_gather_a_layer``)."""
    lowered, leaf, experts = _glm_program(one_chip, program, monkeypatch)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert len(re.findall(r'op_name="[^"]*moe\.experts[^"]*pallas_call',
                          text)) >= 3
    assert _weight_copies(text) == []
    sizes = {n for shape in (experts, leaf)
             for n in [shape[0] * shape[1] * shape[2]]}
    for m in re.finditer(r"= bf16\[([\d,]+)\]\S* (?:copy|transpose)\(", text):
        n = 1
        for x in m.group(1).split(","):
            n *= int(x)
        assert n not in sizes, m.group(0)
    if program == "decode_steps":
        _one_gather_a_layer(text, leaf)
    # donated caches are updated in place and nothing leaf-sized is made:
    # stored [B, Lmax, 1, 576] the leaf was copied whole four times a run
    # (768 MB of temporaries at 2 layers; now 8)
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 2 ** 20


def test_latent_read_keeps_the_flat_view_of_an_uneven_span(one_chip,
                                                           monkeypatch):
    """The fall-back geometry: a span of 4,600 rows, which the 16 rows of a
    bfloat16 tile do not divide, compiles too — through the flat
    ``[B * Lmax, 1, R]`` view, one read a layer (a ``gather``, or the loop
    of window copies into ONE ``[8 slots, 256 rows, 1, R]`` buffer in fast
    memory that the compiler makes of it), and no grouped view."""
    lowered, leaf, _ = _glm_program(one_chip, "decode_steps", monkeypatch,
                                    lmax=4600)
    compiled = lowered.compile()
    text = compiled.as_text()
    flat = "bf16[%d,1,%d]" % (leaf[0] * leaf[1], leaf[2])
    reads = [ln for ln in text.split("\n") if " gather(" in ln
             and flat in ln] + re.findall(
        r"ROOT %%dynamic-update-slice\S* = bf16\[8,256,1,%d\]" % leaf[2],
        text)
    assert len(reads) == 2, reads
    assert not re.search(r"= bf16\[\d+,16,%d\]" % leaf[2], text)
    assert len(_chunk_loops(text)) == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 2 ** 20


# A prefill run of TWO chunks (PERF.md, PR 34: the chunks a scheduler step
# spends on one prompt ride in one run).  Temporaries of the compiled run
# at 2 layers, MB: (one chunk, two chunks, the ceiling held here).
_WIDE_TEMP_MB = {"mistral7b_serve_long": (38.6, 59.0, 64),
                 "glm47flash_serve": (7.9, 9.4, 16)}


def _own_instructions(text):
    """The lines of a compiled text's ENTRY computation and of its loops'
    bodies and conditions: the instructions that run on their own, each a
    pass over its operands (what a fused computation holds is not)."""
    comps = {m.group(1): m.group(2).split("\n") for m in re.finditer(
        r"\n(?:ENTRY )?(%?[\w.\-]+) \([^\n]*\) -> [^\n]* \{\n(.*?)\n\}",
        text, re.S)}
    names = [re.search(r"\nENTRY (%?[\w.\-]+) ", text).group(1)]
    names += sorted(set(re.findall(r"(?:body|condition)=(%[\w.\-]+)", text)))
    return [ln for name in names for ln in comps[name]]


@pytest.mark.parametrize("config", sorted(_WIDE_TEMP_MB))
def test_two_chunk_prefill_run_reads_weights_as_stored(one_chip, config,
                                                       monkeypatch):
    """The ``[1, 2 * 256]`` run of the prefill program at the rag and the
    GLM cell's geometry is the one-chunk program at twice the rows and
    nothing else: nothing of a weight's size is copied, transposed or
    converted on its own (outside a fusion only activations are: the
    run's rows are one of their dims), each
    stacked expert tensor is an operand of ONE grouped product and of
    nothing else, and the temporaries stay under the ceiling (a weight
    materialised in another order or dtype is 8 MB at the least)."""
    rows = 2 * T_PREFILL
    if config == "glm47flash_serve":
        lowered, _, experts = _glm_program(one_chip, "prefill_chunk",
                                           monkeypatch, rows=rows)
    else:
        lowered = _mistral_program(one_chip, "prefill_chunk", 16, 4096,
                                   rows=rows)
    compiled = lowered.compile()
    text = compiled.as_text()
    relayout = re.compile(
        r"= \w+\[([\d,]+)\]\S* (?:copy|transpose|convert)\(")
    for ln in _own_instructions(text):
        m = relayout.search(ln)
        if m is None:
            continue
        dims = [int(x) for x in m.group(1).split(",")]
        size = functools.reduce(lambda a, b: a * b, dims)
        assert size < 2 ** 22 or rows in dims, ln[:160]
    if config == "glm47flash_serve":
        for leaf in ("e_gate", "e_up", "e_down"):
            uses = [ln for ln in text.split("\n")
                    if re.search(r"[(,] ?%%params\S*%s\S*[,)]" % leaf, ln)]
            assert len(uses) == 1, (leaf, [u[:120] for u in uses])
            assert "tpu_custom_call" in uses[0] and re.search(
                r'op_name="[^"]*moe\.experts[^"]*pallas_call', uses[0])
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < _WIDE_TEMP_MB[config][2] * 2 ** 20, temp


# Xing4.0-29B-A4B's widths (the benchmark's xing4_reason_steady cell): GLM's
# two programs with four residual streams under a hyper-connection, a dense
# and an expert layer at the cell's geometry (64 slots x 2,304 rows).
# Instructions that run on their own under ``hc.coeff``, a sub-layer:
# (decode, the two-chunk prefill run).  The Sinkhorn iteration written on
# [rows, 4, 4] arrays with sum(axis) compiles to (88, 91).
_HC_COEFF_OWN = (25, 28)
_XING_TEMP_MB = {"decode_steps": (13.7, 32), "prefill_chunk": (39.4, 64)}


@pytest.mark.parametrize("program", ["decode_steps", "prefill_chunk"])
def test_hyper_connected_programs_compile_in_place(one_chip, program,
                                                   monkeypatch):
    """The decode program and the ``[1, 2 * 256]`` prefill run of the Xing4
    cell compile for the chip at published widths (hidden 3584, 32 heads,
    64 experts of [3584, 1024], four streams of 20 Sinkhorn steps): nothing
    of a weight's size and nothing that holds the whole state (a dim of
    4 x 3584) is copied, transposed or converted on its own, the
    coefficients stay a stated small number of kernels a sub-layer, each
    stacked expert tensor is an operand of ONE grouped product, and the
    temporaries stay under the ceiling."""
    from paddle_tpu.models.xing4 import Xing4Config

    c = Xing4Config(num_hidden_layers=2, first_k_dense_replace=1)
    rows = 2 * T_PREFILL
    lowered, leaf, experts = _glm_program(
        one_chip, program, monkeypatch, lmax=2304, rows=rows, config=c)
    compiled = lowered.compile()
    text = compiled.as_text()
    own = _own_instructions(text)
    relayout = re.compile(
        r"= \w+\[([\d,]+)\]\S* (?:copy|transpose|convert)\(")
    state = c.hc_mult * c.hidden_size
    for ln in own:
        m = relayout.search(ln)
        if m is None:
            continue
        dims = [int(x) for x in m.group(1).split(",")]
        size = functools.reduce(lambda a, b: a * b, dims)
        assert state not in dims, ln[:160]
        assert size < 2 ** 22 or rows in dims, ln[:160]
    coeff = [ln for ln in own if re.search(
        r'op_name="[^"]*hc\.coeff', ln) and re.search(
        r" (?:fusion|reduce|convolution|custom-call|copy)\(", ln)]
    # two layers, two sub-layers each; a multi-output fusion of the
    # iteration carries no op_name and is not counted in either form
    ceiling = _HC_COEFF_OWN[program == "prefill_chunk"]
    assert 0 < len(coeff) <= 4 * ceiling, len(coeff)
    for name in ("hc.read", "hc.write"):
        assert re.search(r'op_name="[^"]*%s' % re.escape(name), text)
    # (the decode program hands its operands through the step loop's
    # tuple, so there a leaf's name shows on that tuple too)
    for leaf_name in ("e_gate", "e_up", "e_down"):
        uses = [ln for ln in text.split("\n") if " tuple(" not in ln
                and re.search(r"[(,] ?%%params\S*%s\S*[,)]" % leaf_name, ln)]
        if program == "prefill_chunk":
            assert len(uses) == 1, (leaf_name, [u[:120] for u in uses])
            assert "tpu_custom_call" in uses[0] and re.search(
                r'op_name="[^"]*moe\.experts[^"]*pallas_call', uses[0])
    assert len(re.findall(r'op_name="[^"]*moe\.experts[^"]*pallas_call',
                          text)) >= 3
    if program == "decode_steps":
        # the latent read at this cell's geometry (64 x 2,304, 32 heads)
        _one_gather_a_layer(text, leaf)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < _XING_TEMP_MB[program][1] * 2 ** 20, temp


# The decode program's cache read (PERF.md, PR 30).  Counts of the whole
# compiled text at 2 layers: (fusion, while, custom-call).  PARENT: commit
# 16e570b (PR 28's tree: one batch-wide chunk loop a layer), read by this
# same test's rule; CEILING: what PR 30's per-slot read compiles to.
_DECODE_SIZE = {
    "32x2048": dict(parent=(105, 3, 12), ceiling=(122, 3, 18)),
    "16x4096": dict(parent=(105, 3, 9), ceiling=(122, 3, 18)),
    "64x1024": dict(parent=(149, 3, 14), ceiling=(168, 3, 25)),
}
_RELAYOUT = re.compile(
    r"= bf16\[([\d,]+)\]\S* (?:copy|transpose)\(")


def _computations(text):
    """``[(name, is a fused computation, its lines)]`` of a compiled text."""
    out = []
    for m in re.finditer(r"\n(%?[\w.\-]+) \([^\n]*\) -> [^\n]* \{\n(.*?)\n\}",
                         text, re.S):
        out.append((m.group(1), "fused_computation" in m.group(1),
                    m.group(2).split("\n")))
    return out


@pytest.mark.parametrize("geometry", sorted(_DECODE_SIZE))
def test_decode_program_reads_the_cache_in_place_and_stays_small(
        one_chip, geometry):
    """The set-up budget's guard off the chip (ISSUE 30), at the three
    serving geometries.

    (a) No ``copy`` / ``transpose`` of a whole K/V cache leaf anywhere: a
    gather that indexes slot and position apart, or slot-by-slot slices,
    makes the compiler copy every layer's cache into another order before
    the loop (seen chipless, PR 29 and PR 30; 268 MB a leaf at 32 x 2048).
    The gathered CHUNK is still transposed to ``[slots, Hkv, C, D]`` for
    the two dots — the MXU wants a head's ``[C, D]`` matrix and the cache
    stores a position's ``[Hkv, D]`` tile — but only inside fused
    computations (the gather's and the dots': fast memory, no pass over
    HBM of its own), at most 4 a layer (the parent: 2 a layer, over the
    whole batch's chunk).
    (b) Size: ONE ``while`` a layer, as the parent (PR 29 had four and
    cost 0.8 s of set-up); fusions and custom calls within the recorded
    ceiling — above the parent's by 5 fusions and one gather marker a
    layer and 7 fusions a step (the slot order): +12% at 16 layers.
    Whether that fits the set-up budget is the chip's to say (PERF.md)."""
    if geometry == "64x1024":
        lowered, _ = _falcon_program(one_chip, "decode_steps")
        batch, lmax, hkv = 64, 1024, 4
    else:
        batch, lmax = (int(x) for x in geometry.split("x"))
        lowered = _mistral_program(one_chip, "decode_steps", batch, lmax)
        hkv = M_NKV
    text = lowered.compile().as_text()
    leaf = batch * lmax * hkv * D
    chunks = 0
    for name, fused, lines in _computations(text):
        for ln in lines:
            m = _RELAYOUT.search(ln)
            if m is None:
                continue
            dims = [int(x) for x in m.group(1).split(",")]
            size = 1
            for x in dims:
                size *= x
            assert size < leaf, f"a whole cache leaf is re-laid-out: {ln[:120]}"
            if 256 in dims and hkv in dims and D in dims:
                chunks += 1
                assert fused, f"a K/V chunk re-laid-out on its own: {ln[:120]}"
    assert 0 < chunks <= 4 * M_LAYERS
    size = tuple(len(re.findall(r" %s\(" % k, text))
                 for k in ("fusion", "while", "custom-call"))
    want = _DECODE_SIZE[geometry]
    assert size[1] == want["parent"][1]
    assert all(a <= b for a, b in zip(size, want["ceiling"])), size


# The benchmark's train cell (``benchmark/configs/mistral7b_train.json``:
# Mistral-7B's widths, 4 x 4096 tokens, every layer recomputed, int8 / bf16
# AdamW moments), built by the benchmark's own code with the seeded weights
# left out: the model's initial arrays on the CPU give the shapes and
# nothing runs.  The platform gates (``flash_attention.available``, the
# kernels' ``interpret`` rule, the q8 AdamW gate) are steered to their TPU
# branch for the trace alone.
TRAIN_BYTES_LINE = 15.5e9
_PALLAS_CALL = re.compile(r'custom-call\(.*op_name="[^"]*/(\w+)/pallas_call"')


def _train_cell(mp, layers=None):
    """``(architecture file, configuration)`` of the train cell, at
    ``layers`` of its layers if given."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mp.syspath_prepend(root)
    from benchmark.lib import weights
    from benchmark.models import llama as arch

    mp.setattr(weights, "place_into", lambda *a: None)
    with open(os.path.join(root, "benchmark", "configs",
                           "mistral7b_train.json")) as f:
        config = json.load(f)
    tr = config["train"]
    assert tr["recompute"] and tr["recompute_layers"] is None
    if layers is not None:
        config["num_hidden_layers"] = layers
    return arch, config


def _attention_kernels(text, layers):
    """One forward attention custom call a layer, and the backward's three
    kernels once a layer, in a compiled text that still recomputes."""
    calls = collections.Counter(_PALLAS_CALL.findall(text))
    assert calls["flash_attention_fwd"] == layers
    for kernel in ("bwd_delta", "bwd_dkv", "bwd_dq"):
        assert calls["flash_attention_" + kernel] == layers
    assert "rematted_computation/mlp" in text


def test_gradient_recomputes_no_attention_forward(one_chip, monkeypatch):
    """The loss's gradient at published widths and 2 layers holds ONE
    forward attention custom call a layer: each layer's checkpoint keeps
    the kernel's ``out`` and ``lse`` (``models/llama.py::_RECOMPUTE_KEEPS``),
    so the recompute inside the backward has no second one (the parent,
    and ``recompute_policy="full"``: two a layer, 43 ms of a 1,390 ms step
    on the chip — PERF.md, PR 32)."""
    from paddle_tpu.autograd import engine
    from paddle_tpu.tensor.tensor import Tensor

    arch, config = _train_cell(monkeypatch, layers=M_LAYERS)
    tr = config["train"]
    model = arch.build(config, 0, tr["seq"], recompute=True,
                       loss_chunk_size=tr["loss_chunk_size"])
    params, buffers = model.functional_state()

    def loss_of(ps, ids):
        with engine.no_grad():
            return model.functional_call(
                ps, buffers, Tensor(ids), Tensor(ids)).data

    ids = jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), jnp.int32,
                               sharding=one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _compile(jax.value_and_grad(loss_of),
                        _shapes(params, one_chip), ids)
    _attention_kernels(compiled.as_text(), M_LAYERS)


@pytest.mark.slow
def test_train_step_fits_the_chip_without_compiler_remat(one_chip,
                                                         monkeypatch):
    """The cell's whole step (6 layers, optimizer and all) as
    ``build_step`` makes it: arguments + temporaries at or under 15.5 GB of
    the 16.9 the runtime offers (ISSUE 32's line; 14.31 GB, and the
    parent's program 13.98 by this count), and NO instruction
    rematerialized by the compiler on its own.  That second line is the
    one that bites: with ``q`` kept too the step is 14.61 GB, XLA re-runs
    MLP products (31 ``.remat`` instructions) and the chip reads ``mlp``
    +50 ms — which is why the kept set stops at ``out`` + ``lse``.  Marked
    slow (80 s alone, and the compiler's threads slow the other workers of
    a tier-1 run, whose limit has little room): run it by hand in a PR
    that changes what the train step keeps alive —
    ``pytest tests/test_chip_compile.py -m slow``."""
    arch, config = _train_cell(monkeypatch)
    from benchmark.drivers.train import build_step

    tr = config["train"]
    _, step = build_step(arch, config, 0)
    ids = jnp.zeros((tr["batch"], tr["seq"]), jnp.int32)
    operands = _shapes(step._operands(1, (ids, ids)), one_chip)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = step._jitted.lower(*operands).compile()
    text, m = compiled.as_text(), compiled.memory_analysis()
    nbytes = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"compiled train step: {nbytes / 1e9:.2f} GB")
    _attention_kernels(text, config["num_hidden_layers"])
    assert ".remat" not in text
    assert nbytes <= TRAIN_BYTES_LINE
