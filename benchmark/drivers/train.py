"""Driver of the ``train`` kind: the callable ``build_train_step`` returns
(forward, backward through flash attention / fused RoPE / chunked CE, and
the AdamW update, one XLA program), stepped back to back on one chip.

Set-up builds ONE step object, drives it from the seed through its first
three steps — through the window's own call and feed — and hands that same
object to the window.  ``correct`` follows those first steps with the plain
float32 reference (the configuration's ``models/<model>.py``) once the window has closed and the
step's state is freed: each step's loss, the norm of the first gradient as
the optimizer got it (from its first-moment state after one step) and the
norm of the parameters' change after two updates, both by the worst leaf.
"""
import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import harness

FIRST_STEPS = 3
UPDATES_FOLLOWED = 2


def opt_tuple(o):
    return (float(o["learning_rate"]), float(o["beta1"]), float(o["beta2"]),
            float(o["epsilon"]), float(o["weight_decay"]))


def build_step(arch, config, seed):
    """(model, step): the compiled step with its state."""
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.static.functionalize import build_train_step

    tr, o = config["train"], config["optimizer"]
    model = arch.build(
        config, seed, tr["seq"], recompute=tr["recompute"],
        loss_chunk_size=tr["loss_chunk_size"],
        recompute_layers=tr["recompute_layers"])
    opt = AdamW(learning_rate=o["learning_rate"], beta1=o["beta1"],
                beta2=o["beta2"], epsilon=o["epsilon"],
                parameters=model.parameters(),
                weight_decay=o["weight_decay"],
                moment_dtype=o["moment_dtype"])
    return model, build_train_step(model, None, opt)


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32))))


@functools.partial(jax.jit, static_argnames=("block",))
def _norm_q8(codes, scale, block):
    """Norm of a blockwise-int8 array: codes x the scale of their block."""
    flat = codes.reshape(-1).astype(jnp.float32)
    n, nb = flat.size, scale.shape[0]
    full = jnp.pad(flat, (0, nb * block - n)).reshape(nb, block) * scale[:, None]
    return jnp.linalg.norm(full.reshape(-1)[:n])


def first_grad_norms(arch, step, beta1, q8_block):
    """Per-leaf norm of the first gradient as the optimizer got it: after
    ONE step the first moment is (1 - beta1) x gradient."""
    scales = step._states.get("moment1@scale", {})
    out = {}
    for name, m in step._states["moment1"].items():
        norm = (_norm_q8(m, scales[name], block=q8_block) if name in scales
                else _norm(m))
        out[arch.locate(name)] = float(norm) / (1 - beta1)
    return out


def change_norms(arch, step, config, seed):
    """Per-leaf norm of (parameters now - the seed's initial weights); the
    initial weights are made again from the seed, a layer at a time."""
    groups = {}
    for name, p in step._params.items():
        group, leaf = arch.locate(name)
        groups.setdefault(group, []).append((leaf, p))
    out = {}
    for group, leaves in groups.items():
        init = arch.initial_weights(config, seed, group)
        for leaf, p in leaves:
            out[(group, leaf)] = float(_diff_norm(p, init[leaf]))
    return out


def first_steps(arch, step, config, seed, data):
    """Drive the step through its first three steps — the window's own call
    and feed — reading what ``correct`` compares."""
    o = config["optimizer"]
    seen = {"losses": []}
    for k in range(FIRST_STEPS):
        loss = step(data[k % len(data)], data[k % len(data)])
        jax.block_until_ready(loss.data)
        seen["losses"].append(float(loss.numpy()))
        if k == 0:
            seen["grad_norms"] = first_grad_norms(arch, step, o["beta1"],
                                                  o["q8_block"])
        if k + 1 == UPDATES_FOLLOWED:
            seen["change_norms"] = change_norms(arch, step, config, seed)
    return seen


def measure(step, data, seconds, trace_dir=None, trace_s=0.0):
    """Steps back to back for ``seconds``, one dispatched ahead of the one
    awaited; the window ends when the last step started has finished.  A
    traced run waits for the step in flight before it starts the profiler,
    so the traced sub-window holds whole steps only."""
    tracing, traced = False, None
    n, prev = 0, None
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if trace_dir and not tracing and traced is None \
                and now >= seconds - trace_s:
            if prev is not None:
                jax.block_until_ready(prev.data)
            harness.start_trace(trace_dir)
            tracing, t_a = True, time.perf_counter()
        batch = data[(FIRST_STEPS + n) % len(data)]
        if tracing:
            with jax.profiler.TraceAnnotation("bench.train_step"):
                loss = step(batch, batch)
                if prev is not None:
                    jax.block_until_ready(prev.data)
        else:
            loss = step(batch, batch)
            if prev is not None:
                jax.block_until_ready(prev.data)
        prev = loss
        n += 1
    if tracing:
        with jax.profiler.TraceAnnotation("bench.train_step"):
            jax.block_until_ready(prev.data)
        traced = (t_a, time.perf_counter())
        jax.profiler.stop_trace()
    else:
        jax.block_until_ready(prev.data)
    return {"steps": n, "window_s": time.perf_counter() - t0,
            "last_loss": float(prev.numpy()), "traced": traced}


def leaf_label(k):
    return f"{k[0]}/{k[1]}"


def check(compared, arch, config, seed, data, seen, compiles_in_window,
          quant=None, rows=None):
    """Follow the first steps with the reference and fill ``compared``."""
    lim = config["check"]
    compared.add("compiles_in_window", compiles_in_window, 0)
    t0 = time.perf_counter()
    ref = arch.train_reference(
        config, seed, [np.asarray(d) for d in data],
        opt_tuple(config["optimizer"]), updates=UPDATES_FOLLOWED,
        quant=quant, rows=rows)
    harness.say("reference", seconds=round(time.perf_counter() - t0, 2),
                losses=[round(x, 5) for x in ref["losses"]],
                program_losses=[round(x, 5) for x in seen["losses"]])
    for k, (a, b) in enumerate(zip(seen["losses"], ref["losses"]), 1):
        compared.add(f"loss_gap_step{k}", abs(a - b) / abs(b),
                     lim[f"loss_gap_step{k}"])
    gap, where = harness.worst_leaf_gap(seen["grad_norms"],
                                          ref["grad_norms"])
    harness.say("reference", grad_norm_worst_leaf=leaf_label(where))
    compared.add("grad_norm_gap", gap, lim["grad_norm_gap"])
    # leaves whose gradient is nought to rounding in the reference move
    # under Adam by round-off alone: left out of the change by a rule on
    # the reference's gradient, not by name
    med = float(np.median(list(ref["grad_norms"].values())))
    skip = {k for k, g in ref["grad_norms"].items() if g < 1e-3 * med}
    gap, where = harness.worst_leaf_gap(seen["change_norms"],
                                          ref["change_norms"], skip)
    harness.say("reference", change_worst_leaf=leaf_label(where),
                leaves_left_out=sorted(map(leaf_label, skip)))
    compared.add("param_change_gap", gap, lim["param_change_gap"])
    return ref


def run(files, cell, config, traffic, seed, seconds, trace, events, t_start,
        wrap_step=None):
    import paddle_tpu as paddle

    gen = files.named("generators", traffic["generator"])
    arch = files.named("models", config["model"])
    tr = config["train"]
    t = time.perf_counter()
    model, step = build_step(arch, config, seed)
    if wrap_step is not None:           # the fault tests only
        step = wrap_step(step)
    host = gen.batches(traffic, seed, tr["batch"], tr["seq"],
                       config["vocab_size"])
    data = [paddle.to_tensor(b, dtype="int64") for b in host]
    build_s = time.perf_counter() - t
    t = time.perf_counter()
    seen = first_steps(arch, step, config, seed, data)
    harness.say("setup", build_s=round(build_s, 2),
                first_steps_s=round(time.perf_counter() - t, 2),
                losses=[round(x, 5) for x in seen["losses"]],
                **events.snapshot())
    trace_dir = harness.fresh_trace_dir(cell) if trace else None
    compiles0 = events.compiles
    setup_s = time.perf_counter() - t_start
    rec = measure(step, data, seconds, trace_dir,
                  trace_s=min(float(traffic.get("trace_seconds", 4.0)),
                              seconds / 2))
    compiles_in_window = events.compiles - compiles0
    tokens = rec["steps"] * tr["batch"] * tr["seq"]
    peak = harness.memory_peak_bytes(cell["chips"])
    harness.say("window", window_s=round(rec["window_s"], 3),
                steps=rec["steps"], tokens=tokens,
                last_loss=round(rec["last_loss"], 5),
                compiles_in_window=compiles_in_window)
    # free the step's state before the reference runs
    del step, model, data
    gc.collect()

    compared = harness.Compared()
    check(compared, arch, config, seed, host, seen, compiles_in_window)
    out = {
        "compared": compared, "attempted": rec["steps"], "failed": 0,
        "memory_peak_bytes": peak,
        "values": {"train_tokens_per_s": tokens / rec["window_s"],
                   "setup_s": setup_s},
        "layer_values": {},
    }
    if trace:
        harness.read_layers(files, cell, trace_dir, {
            "kind": "train", "config": config, "traffic": traffic,
            "record": rec, "model": arch.sizes(config)}, out)
    return out
