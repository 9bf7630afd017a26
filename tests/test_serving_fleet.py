"""Multi-process disaggregated fleet (serving/worker.py +
serving/launch.py): real worker processes, real UDS sockets.

The acceptance properties:

* a config-launched 2-process 1P+1D fleet streams BYTE-IDENTICAL
  tokens to the colocated single-engine reference, over a real
  ``SocketTransport`` wire;
* the warm decode worker adopts a second wave at ZERO decode retraces
  (the handoff changes block-table values, never program shapes) —
  proved from the worker's own compile-cache counters across waves;
* ``close()`` drains gracefully: every worker process exits rc 0;
* (slow) SIGKILLing a decode worker mid-stream loses nothing — the
  parent re-prefills orphans onto the survivor/respawn byte-identically
  and ``serving_worker_restarts_total`` counts the respawn.

Everything here spawns subprocesses (~seconds of jax import each), so
the tier-1 portion is one launch reused across properties.
"""
import os
import signal

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import MetricsRegistry
from paddle_tpu.serving import (
    FaultPlan, FleetConfig, Request, ServingEngine, launch,
)

GEOM = dict(batch_size=3, max_len=128, decode_chunk=16, prefill_chunk=16,
            instrument=False, recorder=False, kv_block=16,
            max_live_tokens=3 * 128)


def _reference(prompts, max_new):
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(dtype="float32"))
    model.eval()
    eng = ServingEngine(model, **GEOM)
    reqs = [eng.submit(Request(p, max_new)) for p in prompts]
    eng.run()
    eng.close()
    return [list(r.output_ids) for r in reqs]


def _prompts(rng, sizes):
    return [rng.integers(1, 2000, size=int(s)).astype(np.int32)
            for s in sizes]


class TestFleetSmoke:
    def test_two_process_fleet(self, tmp_path):
        rng = np.random.default_rng(5)
        wave1 = _prompts(rng, [21, 37, 9])
        wave2 = _prompts(rng, [28, 45])
        ref1 = _reference(wave1, 12)
        ref2 = _reference(wave2, 12)

        cfg = FleetConfig(engine=GEOM, n_prefill=1, n_decode=1,
                          heartbeat_s=0.5, ready_timeout_s=300,
                          workdir=str(tmp_path))
        with launch(cfg, instrument=False) as fleet:
            coord = fleet.coordinator

            got = [coord.submit(Request(p, 12)) for p in wave1]
            coord.run(stall_timeout=120)
            assert [list(r.output_ids) for r in got] == ref1
            assert all(r.status == "done" for r in got)

            d0 = fleet.handles["decode0"]
            traces1 = d0.request({"cmd": "stats"})["stats"]["traces"]

            # second wave against the WARM fleet: byte identity again,
            # and the decode worker compiles nothing new — migration
            # changes block-table values, never program shapes
            got2 = [coord.submit(Request(p, 12)) for p in wave2]
            coord.run(stall_timeout=120)
            assert [list(r.output_ids) for r in got2] == ref2
            traces2 = d0.request({"cmd": "stats"})["stats"]["traces"]
            assert traces2 == traces1, (
                f"decode retraced across waves: {traces1} -> {traces2}")

            # stats aggregate across live workers
            st = coord.stats()
            assert st["workers_dead"] == 0
            assert set(st["workers"]) == {"prefill0", "decode0"}
            assert st["workers"]["decode0"]["pending_chains"] == 0

            procs = {h.name: h.proc for h in fleet.handles.values()}
        # context exit closed the fleet: graceful drain, rc 0 everywhere
        for name, proc in procs.items():
            assert proc.poll() == 0, (name, proc.poll())
            # each replica logged its cold-start account once, at "ready"
            # (observability/compilecache.py::report)
            log = (tmp_path / f"{name}.log").read_text()
            assert log.count("start-up record") == 1, log
            assert "serving.init" in log and "import" in log

    def test_launch_rejects_invalid_config(self, tmp_path):
        cfg = FleetConfig(engine={"batch_size": 2, "max_len": 100,
                                  "kv_block": 16},
                          workdir=str(tmp_path))
        with pytest.raises(ValueError, match="multiple"):
            launch(cfg)


@pytest.mark.slow
class TestFleetFaults:
    def test_decode_kill_recovers_byte_identically(self, tmp_path):
        # 1P+2D; SIGKILL decode0 early: orphans resume as suffix
        # prefills on decode1 and every stream matches the reference
        rng = np.random.default_rng(1)
        prompts = _prompts(rng, [21, 37, 9])
        ref = _reference(prompts, 16)
        reg = MetricsRegistry()
        fp = FaultPlan(worker_kill={40: "decode0"})
        cfg = FleetConfig(engine=GEOM, n_prefill=1, n_decode=2,
                          heartbeat_s=0.5, ready_timeout_s=300,
                          adoption_timeout_s=15.0,
                          workdir=str(tmp_path))
        with launch(cfg, registry=reg, instrument=True,
                    faults=fp) as fleet:
            coord = fleet.coordinator
            got = [coord.submit(Request(p, 16)) for p in prompts]
            coord.run(stall_timeout=120)
            assert [list(r.output_ids) for r in got] == ref
            assert all(r.status == "done" for r in got)
            st = coord.stats()
            assert st["workers_dead"] == 1
            assert fp.stats["worker_kills"] == 1
        prom = reg.to_prometheus()
        assert "serving_orphan_reprefills_total" in prom

    def test_decode_kill_with_respawn(self, tmp_path):
        # 1P+1D with restart_dead_workers: the dead decode worker is
        # respawned under the same name/endpoint and every orphan
        # resumes on the replacement, byte-identically
        rng = np.random.default_rng(1)
        prompts = _prompts(rng, [21, 37, 9])
        ref = _reference(prompts, 16)
        reg = MetricsRegistry()
        fp = FaultPlan(worker_kill={40: "decode0"})
        cfg = FleetConfig(engine=GEOM, n_prefill=1, n_decode=1,
                          heartbeat_s=0.5, ready_timeout_s=300,
                          restart_dead_workers=True,
                          adoption_timeout_s=10.0,
                          workdir=str(tmp_path))
        with launch(cfg, registry=reg, instrument=True,
                    faults=fp) as fleet:
            coord = fleet.coordinator
            got = [coord.submit(Request(p, 16)) for p in prompts]
            coord.run(stall_timeout=120)
            assert [list(r.output_ids) for r in got] == ref
            assert all(r.status == "done" for r in got)
            procs = {h.name: h.proc for h in fleet.handles.values()}
        prom = reg.to_prometheus()
        assert 'serving_worker_restarts_total{coordinator="fleet0"} 1' \
            in prom
        # the respawned worker drains gracefully too
        for name, proc in procs.items():
            assert proc.poll() == 0, (name, proc.poll())

    def test_sigterm_is_graceful_drain(self, tmp_path):
        # SIGTERM (the deployment's stop signal) flips the worker into
        # draining; with nothing in flight it exits 0 on its own
        rng = np.random.default_rng(2)
        prompts = _prompts(rng, [21, 9])
        ref = _reference(prompts, 8)
        cfg = FleetConfig(engine=GEOM, n_prefill=1, n_decode=1,
                          heartbeat_s=0.5, ready_timeout_s=300,
                          workdir=str(tmp_path))
        with launch(cfg, instrument=False) as fleet:
            coord = fleet.coordinator
            got = [coord.submit(Request(p, 8)) for p in prompts]
            coord.run(stall_timeout=120)
            assert [list(r.output_ids) for r in got] == ref
            handles = list(fleet.handles.values())
            for h in handles:
                h.proc.send_signal(signal.SIGTERM)
            for h in handles:
                h.proc.wait(timeout=60)
                assert h.proc.returncode == 0, (h.name,
                                                h.proc.returncode)
