"""Each driver end to end at toy size: the last line parses, names the
device, and the no-chip path exits non-zero."""
import json

import pytest

from benchmark import run


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_driver(toy_root, capsys, trace):
    run.main(["--workload", "toy_chat", "--seed", "3000000019", "--seconds",
              "2", "--trace", str(trace)], require_chip=False, root=toy_root)
    line = last_line(capsys)
    assert list(line)[-1] == "compared" and line["correct"] is True
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["attempted"] > 0 and line["failed"] == 0
    if trace:
        assert "decode_batch_mean" in line["metrics"]
        # nothing ran on a device: the device readers return nothing, and
        # the harness leaves them out rather than printing a 0
        assert "decode_step_ms" not in line["metrics"]
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                        "serve_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("trace", [0, 1])
def test_train_driver(toy_root, capsys, trace):
    run.main(["--workload", "toy_steps", "--seed", "2147483659", "--seconds",
              "1", "--trace", str(trace)], require_chip=False, root=toy_root)
    line = last_line(capsys)
    assert line["correct"] is True, line["compared"]
    if not trace:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_same_seed_same_inputs(toy_root):
    from benchmark.lib import harness

    files = harness.Files(toy_root)
    _, config, traffic = files.cell("toy_chat")
    gen = files.named("generators", "open_loop")
    a = gen.schedule(traffic, 2**31 + 5, 3.0, 256)
    b = gen.schedule(traffic, 2**31 + 5, 3.0, 256)
    c = gen.schedule(traffic, 2**31 + 6, 3.0, 256)
    assert all(x[0] == y[0] and (x[1] == y[1]).all() for x, y in zip(a, b))
    # another seed: the same arrival pattern and lengths, other token ids
    assert [(x[0], len(x[1]), x[2]) for x in a] == [
        (x[0], len(x[1]), x[2]) for x in c]
    assert not all((x[1] == y[1]).all() for x, y in zip(a, c))


def test_no_chip_exits_nonzero(toy_root, capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "toy_chat", "--seed", "1", "--seconds", "1",
                  "--trace", "0"], require_chip=True, root=toy_root)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""
