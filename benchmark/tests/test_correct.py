"""``correct`` has to come out false when it should: the lower-precision
control, and the timed path broken underneath — each fault a cell can
have.  Toy size, CPU; the readings a limit was set from are the chip's
(PERF.md)."""
import json

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import harness, llama_ref
from benchmark.lib import weights as W


def drive(toy_root, workload, capsys, **driver_kw):
    """A whole run with the look for a chip skipped and ``driver_kw``
    (a fault planted under the timed path) handed to the driver."""
    files = harness.Files(toy_root)
    cell, config, traffic = files.cell(workload)
    device, events = harness.start(1, require_chip=False)
    import time

    out = files.named("drivers", config["kind"]).run(
        files=files, cell=cell, config=config, traffic=traffic,
        seed=2147483777, seconds=1.0, trace=False, events=events,
        t_start=time.perf_counter(), **driver_kw)
    out["compared"].print()
    return harness.result_line(files, workload, False, out, device)


# ----------------------------------------------------------------- serving
def test_serving_sound_run_is_correct(toy_root, capsys):
    assert drive(toy_root, "toy_chat", capsys)["correct"] is True


def test_serving_token_altered_where_it_is_produced(toy_root, capsys):
    def plant(engine):
        emit = engine._emit

        def altered(slot, toks):
            return emit(slot, [(int(t) + 1) % 256 for t in toks])
        engine._emit = altered

    line = drive(toy_root, "toy_chat", capsys, before_window=plant)
    assert line["correct"] is False
    assert line["compared"]["logit_gap_max"]["ok"] is False


def test_serving_control_fp8_reads_above_the_limit(toy_root):
    """The control: the reference in float8 in the program's place.  It need
    not decode: at each position of the same prompts and tokens, the gap of
    the token the lower precision puts first is read — and lies above the
    cell's limit, which a sound run stays under (the test above)."""
    files = harness.Files(toy_root)
    _, config, traffic = files.cell("toy_chat")
    serve = files.named("drivers", "serve")
    arch = files.named("models", config["model"])
    rng = np.random.default_rng(5)
    served = [(rng.integers(1, 256, 8).astype(np.int32),
               rng.integers(1, 256, 110)) for _ in range(4)]
    _, ctrl = serve.reference_gaps(arch, config, (128, 110), 9, served,
                                   quants=(None, "fp8"))
    assert ctrl.size == 440
    assert ctrl.max() > 3 * config["check"]["logit_gap_max"]


# ---------------------------------------------------------------- training
class Wrapped:
    """A train step with a fault planted; everything else is the step's."""

    def __init__(self, step, call):
        self._step, self._call = step, call

    def __getattr__(self, name):
        return getattr(self._step, name)

    def __call__(self, x, y):
        return self._call(self._step, x, y)


def state_unchanged(step, x, y):
    import jax
    import jax.numpy as jnp

    keep = jax.tree.map(jnp.copy, (step._params, step._states))
    loss = step(x, y)
    step._params, step._states = keep
    return loss


def half_the_batch(step, x, y):
    half = x.shape[0] // 2
    return step(x[:half], y[:half])


def test_training_sound_run_is_correct(toy_root, capsys):
    assert drive(toy_root, "toy_steps", capsys)["correct"] is True


@pytest.mark.parametrize("fault", [state_unchanged, half_the_batch])
def test_training_faults_come_out_not_correct(toy_root, capsys, fault):
    line = drive(toy_root, "toy_steps", capsys,
                 wrap_step=lambda s: Wrapped(s, fault))
    assert line["correct"] is False, line["compared"]
    if fault is state_unchanged:
        # nothing moved: the change reads 1 by the worst-leaf measure
        assert line["compared"]["param_change_gap"]["value"] == pytest.approx(
            1.0, abs=0.05)


def test_training_control_fp8_comes_out_not_correct(toy_root):
    """The control: the float32 reference against the same reference in
    float8, by the cell's own numbers and limits."""
    files = harness.Files(toy_root)
    _, config, traffic = files.cell("toy_steps")
    train = files.named("drivers", "train")
    arch = files.named("models", config["model"])
    gen = files.named("generators", "token_batches")
    tr = config["train"]
    host = gen.batches(traffic, 3, tr["batch"], tr["seq"], 256)
    m = W.model_sizes(config)
    ctrl = llama_ref.train_reference(m, 3, "float32", host,
                                     train.opt_tuple(config["optimizer"]),
                                     quant="fp8")
    compared = harness.Compared()
    train.check(compared, arch, config, 3, host, ctrl, 0)
    assert compared.correct is False


def test_reference_gradient_is_jax_grad(toy_root):
    """The hand-written backward of the reference (row by row, layer by
    layer) against ``jax.grad`` of the same forward written in one piece."""
    import jax
    import jax.numpy as jnp

    files = harness.Files(toy_root)
    _, config, _ = files.cell("toy_steps")
    m = W.model_sizes(config)
    dims = W.dims_of(m)
    ids = np.random.default_rng(1).integers(0, 256, (2, 3, 24))
    ref = llama_ref.train_reference(m, 4, "float32", ids,
                                    (1e-3, 0.9, 0.999, 1e-8, 0.0), updates=1)
    params = {"top": W.top_weights(4, dims, "float32"),
              "layers": [W.layer_weights(4, i, dims, "float32")
                         for i in range(2)]}

    def loss(p):
        total = 0.0
        for row in ids[0]:
            h = p["top"]["embed"][row]
            for i in range(2):
                h = llama_ref.layer(p["layers"][i], h, dims, 1e-5, 1e6)
            total += llama_ref._head_loss(p["top"], h, jnp.asarray(row),
                                          1e-5, None)
        return total / (3 * 23)

    value, g = jax.value_and_grad(loss)(params)
    assert ref["losses"][0] == pytest.approx(float(value), rel=1e-5)
    for (group, leaf), n in ref["grad_norms"].items():
        mine = g["top"][leaf] if group == "top" else g["layers"][group][leaf]
        assert n == pytest.approx(float(jnp.linalg.norm(mine)),
                                  rel=1e-4), (group, leaf)
