"""Test configuration.

Tests run on a virtual 8-device CPU platform (the reference's fake_cpu_device /
CustomCPU-plugin testing model, SURVEY.md §4): sharding/collective code paths are
exercised without TPU hardware.  The chip is not reached from here: it is reached
through the chip tool with ``chip_smoke.py`` (.claude/skills/verify/SKILL.md).

jax.config.update works until the backend is actually initialized, whatever the
environment says.
"""
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# numeric tests compare against float64 numpy references; keep MXU-passes at highest
# precision (the per-op tolerance policy: bench/perf paths use bf16 explicitly).
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running workloads (serving mixed-length runs, bench-"
        "shaped tests) excluded from tier-1 via -m 'not slow'")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(2024)
    np.random.seed(2024)
    yield
