"""CPU tests of ``chip_smoke.py``'s plumbing and of the compile-cache
helper.  The script's own run needs a TPU; here its phase functions run at
toy size with the chip-only checks off (the rehearsal of the
on-chip-measurement guide: wrong paths, arguments and control flow are
found at no chip time), and the script itself is shown to FAIL on a CPU
host instead of passing on the wrong device."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke
from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("phase", ["serve", "train", "multichip"])
def test_phase_runs_at_toy_size(phase, capsys, monkeypatch):
    """Each phase function, imported and driven end to end: every check
    it makes off the chip passes, and it reports the device it ran on.
    (The persistent cache stays off: this process is a test worker, and
    the once-a-process record of kernel fallbacks starts empty: the script
    reads it, and an earlier test file of this worker may have filled it.)"""
    from paddle_tpu.ops import paged_attention_pallas as pap

    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "(off under test)")
    monkeypatch.setattr(pap, "_warned", set())
    device, failed = chip_smoke.PHASES[phase](chip_smoke.TOY, chip=False)
    out = capsys.readouterr().out
    assert failed == [], out
    assert device == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                      "count": len(jax.devices())}
    assert f"[{phase}] platform=cpu" in out
    assert "sync_probe" in out or phase == "multichip"
    assert "cache_hits=" in out


def _run_script(cwd, script):
    # the driver's way: `python3 chip_smoke.py`, no arguments
    return subprocess.run([sys.executable, script], cwd=cwd, timeout=600,
                          capture_output=True, text=True,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_script_fails_without_a_tpu(where, tmp_path):
    """No CPU fallback: on a CPU host — and in a directory holding nothing
    else of the repo — the script exits non-zero and its last line says
    ``"ok": false``; it never prints the success object."""
    if where == "alone":
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        r = _run_script(tmp_path, "chip_smoke.py")
    else:
        r = _run_script(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert set(last["failed"]) == {"serve", "train"}
    assert '"ok": true' not in r.stdout


class _Recorder:
    def __init__(self):
        self.updates = []

    def __call__(self, name, value):
        self.updates.append((name, value))


# a cache hit must carry the op names of the code that runs (PERF.md, PR 26)
NAMES_IN_KEY = ("jax_compilation_cache_include_metadata_in_key", True)


def test_compile_cache_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself and the helper
    sets no directory in code (only what the key is made of)."""
    rec = _Recorder()
    monkeypatch.setattr(jax.config, "update", rec)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert rec.updates == [NAMES_IN_KEY]


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(jax.config, "update", rec)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want   # never moves
    assert rec.updates == [NAMES_IN_KEY,
                           ("jax_compilation_cache_dir", want)] * 2
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


class _FakeDevice:
    platform = "tpu"
    device_kind = "TPU v9 imaginary"


def test_device_spec_detect_has_no_silent_default(monkeypatch):
    from paddle_tpu.distributed.auto_parallel.static.tuner import DeviceSpec

    assert DeviceSpec.detect() == DeviceSpec()   # CPU: the stated default
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDevice()])
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        DeviceSpec.detect()
