"""Standalone fleet worker process: one PrefillWorker or DecodeWorker
on its own mesh, driven over a lightweight control channel.

``python -m paddle_tpu.serving.worker <config.json> <role> <idx>`` is
the process entry the fleet launcher (serving/launch.py) spawns.  Each
worker is a full engine in its own process — its own jax platform/
device configuration (set BEFORE jax initializes, the same bootstrap
discipline as tests/_mp_mesh_worker.py), its own compile cache, its own
metrics registry — which is the whole point of disaggregation: the
prefill mesh and the decode mesh stop sharing anything but the KV wire.

Two planes, two sockets:

* **control plane** — a UDS the worker listens on; the parent connects
  and exchanges length-prefixed pickled dicts.  Commands (``submit``,
  ``cancel``, ``stats``, ``healthz``, ``drain``, ``close``) carry a
  ``req`` id and get a matching ``reply``; the worker interleaves
  spontaneous **events** (``ready``, ``first``, ``tokens``,
  ``retired``, ``shadow_failed``, ``adopted``, ``xfer_err``, ``hb``,
  ``drained``) on the same stream.  The parent's ``FleetCoordinator``
  turns these into the familiar ``Replica`` surface.
* **data plane** — serving/transport.py's ``SocketTransport``.  A
  decode worker listens at its configured KV endpoint; a prefill worker
  lazily connects one sender per decode peer and ships each finished
  request's block chain with enough metadata (prompt, budget, first
  token) for the decode side to rebuild the caller's Request and
  ``adopt_prefilled`` it.

The serve loop never blocks on either plane: control reads are
selector-gated with a zero timeout while the engine has work, the KV
sender streams on its background thread, and the decode pump drains
``kv_transfer_recv()`` (complete chains only — the PTL017-sanctioned
non-blocking inbox).  SIGTERM flips the worker into draining: no new
admissions, resident requests run to their terminal status, a
``drained`` event, exit 0.
"""

from __future__ import annotations

import json
import logging
import os
import pickle
import selectors
import signal
import socket
import struct
import sys
import time

_LOG = logging.getLogger(__name__)

_LEN = struct.Struct("<I")
_MAX_MSG = 1 << 28


# ---------------------------------------------------------------------------
# control-plane framing (stdlib-only: launch.py imports these without
# touching jax)
# ---------------------------------------------------------------------------

def send_msg(sock, obj):
    blob = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(_LEN.pack(len(blob)) + blob)


class FrameReader:
    """Incremental parser over a non-blocking socket: feed whatever
    bytes arrived, get complete messages out.  ``eof`` latches when the
    peer closes."""

    def __init__(self):
        self._buf = bytearray()
        self.eof = False

    def feed(self, data):
        if not data:
            self.eof = True
        else:
            self._buf += data

    def messages(self):
        out = []
        while True:
            if len(self._buf) < 4:
                break
            (n,) = _LEN.unpack_from(self._buf, 0)
            if n > _MAX_MSG:
                raise ValueError(f"oversized control frame ({n} bytes)")
            if len(self._buf) < 4 + n:
                break
            out.append(pickle.loads(bytes(self._buf[4:4 + n])))
            del self._buf[:4 + n]
        return out


def pump_socket(sock, reader):
    """Drain whatever the non-blocking socket holds into the reader;
    returns the complete messages that produced."""
    while True:
        try:
            data = sock.recv(65536)
        except (BlockingIOError, InterruptedError):
            break
        except OSError:
            reader.eof = True
            break
        reader.feed(data)
        if not data:
            break
    return reader.messages()


# ---------------------------------------------------------------------------
# the worker process
# ---------------------------------------------------------------------------

class _WorkerProc:
    """One role's serve loop.  Heavy imports (jax, the engine) happen in
    ``start()`` — after ``main()`` pinned the jax platform config."""

    def __init__(self, cfg, role, idx):
        self.cfg = cfg
        self.role = role
        self.idx = int(idx)
        self.name = f"{role}{idx}"
        self.draining = False
        self._ctl_listener = None
        self._ctl = None
        self._reader = FrameReader()
        self._sel = selectors.DefaultSelector()
        self._hb_t = 0.0
        self._events = []

    # ----------------------------------------------------------- bootstrap
    def start(self):
        ctl_path = self.cfg["control"][self.name]
        try:
            os.unlink(ctl_path)
        except FileNotFoundError:
            pass
        self._ctl_listener = socket.socket(socket.AF_UNIX,
                                           socket.SOCK_STREAM)
        self._ctl_listener.bind(ctl_path)
        self._ctl_listener.listen(1)

        from ..observability import MetricsRegistry
        from .metrics import DisaggMetrics
        self.registry = MetricsRegistry()
        self._dm = DisaggMetrics(self.registry, self.name)
        self._build_engine()

        conn, _ = self._ctl_listener.accept()
        conn.setblocking(False)
        self._ctl = conn
        self._sel.register(conn, selectors.EVENT_READ)
        self._event("ready", pid=os.getpid(), role=self.role,
                    pool=self._pool)
        self._flush_events()
        # the cold-start account of this replica: where the seconds before
        # "ready" went, by phase and by (program, compile stage)
        from ..observability.compilecache import report
        _LOG.info("ready; start-up record (s, largest first): %s", "; ".join(
            " ".join(filter(None, (r["name"], r["stage"],
                                   f"{r['seconds']:.3f}")))
            for r in report()[:24]))

    def _build_model(self, m=None):
        import paddle_tpu as paddle
        from ..models.llama import LlamaConfig, LlamaForCausalLM
        if m is None:
            m = self.cfg.get("model", {})
        if m.get("kind", "llama") != "llama" or \
                m.get("preset", "tiny") != "tiny":
            raise ValueError(f"unsupported model spec {m!r}")
        paddle.seed(int(m.get("seed", 0)))
        kw = {}
        if m.get("num_hidden_layers") is not None:
            kw["num_hidden_layers"] = int(m["num_hidden_layers"])
        cfg = LlamaConfig.tiny(dtype=m.get("dtype", "float32"), **kw)
        model = LlamaForCausalLM(cfg)
        model.eval()
        return model

    def _build_engine(self):
        from .disagg import DecodeWorker, PrefillWorker
        from .transport import SocketTransport, pool_spec
        model = self._build_model()
        kw = dict(self.cfg.get("engine", {}))
        kw.update(self.cfg.get(self.role, {}) or {})
        kw["registry"] = self.registry
        spec = kw.pop("spec", None)
        if self.role != "prefill" and spec is not None:
            # launch-config spec block: {"source": ..., "spec_k": ...,
            # "draft_model": {<model spec>}} — the draft model is BUILT
            # here, in the worker process (model objects don't cross the
            # config pipe)
            from .engine import SpecConfig
            if isinstance(spec, dict):
                spec = dict(spec)
                dm = spec.pop("draft_model", None)
                if dm is not None:
                    dm = self._build_model(dict(dm))
                spec = SpecConfig(draft_model=dm, **spec)
            kw["spec"] = spec
        if self.role == "prefill":
            kw.pop("mode", None)
            kw.pop("spec_k", None)
            self.worker = PrefillWorker(model, name=self.name, **kw)
            self.worker._sink = self._on_prefilled
            self._pool = pool_spec(self.worker.engine.kv_manager)
            self._senders = {}          # decode name -> SocketTransport
            self._meta = {}             # rid -> submit metadata
            self._shadow_objs = {}      # rid -> (shadow Request, _)
        else:
            self.worker = DecodeWorker(model, name=self.name, **kw)
            self._pool = pool_spec(self.worker.engine.kv_manager)
            self._kvx = SocketTransport.listen(
                self.cfg["endpoints"][self.name], self._pool,
                name=f"{self.name}-kvx")
            self._pending = []          # chains awaiting adoption
            self._resident = {}         # rid -> Request
            self._tok_out = {}          # rid -> emitted-but-unsent ids
            self._stall_mark = {}       # rid -> first stalled-at
        self.engine = self.worker.engine

    # ----------------------------------------------------- event plumbing
    def _event(self, ev, **kw):
        kw["ev"] = ev
        kw["name"] = self.name
        self._events.append(kw)

    def _flush_events(self):
        if self._ctl is None:
            return
        while self._events:
            msg = self._events.pop(0)
            try:
                send_msg(self._ctl, msg)
            except OSError:
                self._reader.eof = True
                return

    # --------------------------------------------------------- prefill side
    def _on_prefilled(self, worker, shadow, slot, first):
        """The engine's completion hook, fleet edition: emit the first
        token to the parent immediately (TTFT rides the control plane),
        then — unless the token finished the request — export the chain
        and hand it to the decode peer's background sender."""
        meta = self._meta.get(shadow.rid)
        if meta is None:
            return
        first = int(first)
        final = (meta["max_new"] <= 1
                 or (meta.get("eos") is not None
                     and first == int(meta["eos"])))
        if final:
            self._event("first", rid=shadow.rid, token=first, final=True)
            self._meta.pop(shadow.rid, None)
            return
        kv = self.engine.kv_manager
        chain = kv.block_chain(shadow.rid)
        leaves = kv.export_chain(chain)
        meta = dict(meta, first=first)
        try:
            sender = self._sender_for(meta["decode"])
            _, nbytes = sender.send(shadow.rid, leaves, meta=meta)
        except Exception as e:  # noqa: BLE001 — parent re-routes
            self._event("xfer_err", rid=shadow.rid,
                        error=f"{type(e).__name__}: {e}")
            self._meta.pop(shadow.rid, None)
            # The cached sender is poisoned (its peer died or its stream
            # broke mid-chain); evict it so the next chain reconnects —
            # a respawned peer listens at the same endpoint.
            stale = self._senders.pop(meta["decode"], None)
            if stale is not None:
                try:
                    stale.close()
                except Exception:  # noqa: BLE001 — already broken
                    pass
            return
        self._dm.transfer_bytes.inc(nbytes)
        self._meta.pop(shadow.rid, None)  # handed off: nothing left here
        self._event("first", rid=shadow.rid, token=first, final=False,
                    nbytes=nbytes, n_blocks=len(chain))

    def _sender_for(self, decode_name):
        from .transport import SocketTransport
        s = self._senders.get(decode_name)
        if s is None:
            s = SocketTransport.connect(
                self.cfg["endpoints"][decode_name], self._pool,
                name=f"{self.name}->{decode_name}")
            self._senders[decode_name] = s
        return s

    def _sweep_shadows(self):
        for rid, (shadow, _) in list(self._shadow_objs.items()):
            if not shadow.done:
                continue
            del self._shadow_objs[rid]
            if shadow.status != "done":
                self._meta.pop(rid, None)
                self._event("shadow_failed", rid=rid, status=shadow.status)

    # ---------------------------------------------------------- decode side
    def _pump_chains(self):
        """Adopt every complete chain the transport holds; defer the
        rest.  The overlap-stall clock starts the moment a chain is
        in flight while this engine could adopt — the window a blocking
        transport would have stalled the step loop."""
        import numpy as np
        now = time.perf_counter()
        free = self.engine.stats()["slots_occupied"] < \
            self.engine.stats()["slots_total"]
        if free:
            for rid, _meta in self._kvx.inflight_chains():
                self._stall_mark.setdefault(rid, now)
        self._pending.extend(self._kvx.kv_transfer_recv())
        keep = []
        for entry in self._pending:
            rid, meta = entry["rid"], entry["meta"]
            user = entry.get("_user")
            if user is None:
                from .engine import Request
                user = Request(
                    np.asarray(meta["prompt"], dtype=np.int32),
                    int(meta["max_new"]),
                    eos_token_id=meta.get("eos"), rid=rid,
                    slo_class=meta.get("slo_class"),
                    priority=int(meta.get("priority", 0)))
                user.t_submit = now
                user.output_ids.append(int(meta["first"]))
                user.t_first = now
                user.stream_cb = self._collect_tokens
                entry["_user"] = user
            if not self.engine.can_adopt(user):
                keep.append(entry)
                continue
            from .engine import EngineOverloaded
            from .kv_cache import KVPoolExhausted
            try:
                self.engine.adopt_prefilled(user, int(meta["first"]),
                                            entry["leaves"])
            except (EngineOverloaded, KVPoolExhausted):
                keep.append(entry)
                continue
            wire = (entry["t_done"] or now) - entry["t_begin"]
            mark = self._stall_mark.pop(rid, None)
            self._dm.transfer_seconds.observe(wire)
            self._dm.overlap_stall.observe(
                max(0.0, now - mark) if mark is not None else 0.0)
            self._dm.migration("ok")
            self._resident[rid] = user
            self._event("adopted", rid=rid)
        self._pending = keep

    def _collect_tokens(self, req, new_ids):
        self._tok_out.setdefault(req.rid, []).extend(
            int(i) for i in new_ids)

    def _sweep_decode(self):
        for rid, ids in list(self._tok_out.items()):
            if ids:
                self._event("tokens", rid=rid, ids=list(ids))
                ids.clear()
        for rid in list(self._resident):
            u = self._resident[rid]
            if u.done:
                del self._resident[rid]
                self._tok_out.pop(rid, None)
                self._event("retired", rid=rid, status=u.status)

    # ------------------------------------------------------------ commands
    def _handle(self, msg):
        cmd = msg.get("cmd")
        req = msg.get("req")

        def reply(**kw):
            kw.setdefault("ok", True)
            kw["reply"] = req
            try:
                send_msg(self._ctl, kw)
            except OSError:
                self._reader.eof = True

        if cmd == "submit":
            if self.role != "prefill":
                reply(ok=False, etype="ValueError",
                      error="decode workers take chains, not submits")
                return
            if self.draining:
                reply(ok=False, etype="EngineOverloaded",
                      error="worker is draining")
                return
            import numpy as np
            from .engine import Request
            shadow = Request(np.asarray(msg["prompt"], dtype=np.int32), 1,
                             rid=msg["rid"],
                             slo_class=msg.get("slo_class"),
                             priority=int(msg.get("priority", 0)))
            try:
                self.engine.submit(shadow)
            except Exception as e:  # noqa: BLE001 — etype crosses the wire
                reply(ok=False, etype=type(e).__name__, error=str(e))
                return
            self._meta[msg["rid"]] = {
                "prompt": [int(i) for i in msg["prompt"]],
                "max_new": int(msg["max_new"]),
                "eos": msg.get("eos"),
                "slo_class": msg.get("slo_class"),
                "priority": int(msg.get("priority", 0)),
                "decode": msg["decode"],
            }
            self._shadow_objs[msg["rid"]] = (shadow, None)
            reply()
        elif cmd == "cancel":
            found = self.engine.cancel(msg["rid"])
            if self.role == "prefill":
                self._meta.pop(msg["rid"], None)
            else:
                # Drop an un-adopted chain too: the parent gave up on
                # this handoff and re-routed — adopting it later would
                # decode a ghost nobody is listening to.
                before = len(self._pending)
                self._pending = [e for e in self._pending
                                 if e["rid"] != msg["rid"]]
                found = found or len(self._pending) != before
            reply(found=bool(found))
        elif cmd == "stats":
            reply(stats=self._stats())
        elif cmd == "healthz":
            reply(t=time.time(), draining=self.draining)
        elif cmd == "drain":
            self.draining = True
            reply()
        elif cmd == "close":
            self.draining = True
            self._closing = True
            reply()
        else:
            reply(ok=False, etype="ValueError",
                  error=f"unknown command {cmd!r}")

    def _stats(self):
        from ..observability.compilecache import all_monitors
        traces = {}
        for mon in all_monitors():
            for key, n in mon.trace_counts().items():
                traces[key] = traces.get(key, 0) + n
        out = {
            "name": self.name,
            "role": self.role,
            "engine": self.engine.stats(),
            "traces": traces,
            "kv_transfer_p50_s": self._dm.transfer_seconds.percentile(50),
            "overlap_stall_p50_s": self._dm.overlap_stall.percentile(50),
        }
        em = getattr(self.engine, "_m", None)
        if em is not None:
            out["adm_tpot_p95_s"] = em.tpot_admission.percentile(95)
        if self.role == "decode":
            out["transport"] = self._kvx.stats()
            out["pending_chains"] = len(self._pending)
        return out

    # ----------------------------------------------------------- serve loop
    def _has_work(self):
        if self.engine.has_work:
            return True
        if self.role == "decode":
            return bool(self._pending) or bool(self._resident) \
                or bool(self._kvx.inflight_chains())
        return bool(self._meta)

    def serve(self):
        self._closing = False
        hb = float(self.cfg.get("heartbeat_s", 1.0))
        # deadlock watchdog on the serve loop itself: the loop is
        # selector-gated (never sleeps more than 50 ms), so a stale
        # iteration beat means the loop is truly wedged — a deadlocked
        # step dispatch, a blocking handler — and the watchdog dumps
        # every thread's stack through the engine's flight recorder
        from paddle_tpu.observability.watchdog import DeadlockWatchdog
        wd_s = float(self.cfg.get("watchdog_s", 30.0) or 0.0)
        self._wd_beat = time.time()
        wd = None
        if wd_s > 0:
            wd = DeadlockWatchdog(
                lambda: self._wd_beat, stall_after=wd_s,
                recorder=self.engine.recorder, registry=self.registry,
                component=self.name).start()
        try:
            while True:
                self._wd_beat = time.time()
                busy = self._has_work()
                for key, _ in self._sel.select(0 if busy else 0.05):
                    for msg in pump_socket(key.fileobj, self._reader):
                        # host-side control plane: the np.asarray it
                        # reaches converts a submit's prompt list, not
                        # device leaves
                        self._handle(msg)  # tpu-lint: ignore[PTL004]
                if self._reader.eof:
                    # parent went away: drain what is resident and exit
                    self.draining = True
                    self._closing = True
                if self.role == "decode":
                    # chain leaves arrive as numpy off the wire; the
                    # np.asarray here wraps them for import, no device
                    # sync
                    self._pump_chains()  # tpu-lint: ignore[PTL004]
                if self.engine.has_work:
                    self.engine.step()
                if self.role == "decode":
                    self._sweep_decode()
                else:
                    self._sweep_shadows()
                now = time.monotonic()
                if now - self._hb_t >= hb:
                    self._hb_t = now
                    self._event("hb", t=time.time())
                self._flush_events()
                if self.draining and not self._has_work():
                    self._event("drained")
                    self._flush_events()
                    break
        finally:
            if wd is not None:
                wd.stop()
        self.shutdown()

    def shutdown(self):
        try:
            self.engine.close()
        except Exception:  # noqa: BLE001 — best-effort teardown
            pass
        if self.role == "prefill":
            for s in self._senders.values():
                try:
                    s.flush(timeout=5.0)
                except Exception:  # noqa: BLE001
                    pass
                s.close()
        else:
            self._kvx.close()
        self._flush_events()
        for sock in (self._ctl, self._ctl_listener):
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if len(argv) != 3:
        print("usage: python -m paddle_tpu.serving.worker "
              "<config.json> <prefill|decode> <idx>", file=sys.stderr)
        return 2
    cfg_path, role, idx = argv
    with open(cfg_path) as f:
        cfg = json.load(f)
    if role not in ("prefill", "decode"):
        print(f"unknown role {role!r}", file=sys.stderr)
        return 2

    logging.basicConfig(
        level=logging.INFO,
        format=f"%(asctime)s {role}{idx} %(levelname)s %(message)s")

    # jax platform config MUST land before jax initializes a backend —
    # same bootstrap order as tests/_mp_mesh_worker.py
    import jax
    jax.config.update("jax_platforms", cfg.get("platform", "cpu"))
    ndev = int(cfg.get("devices_per_worker", 1))
    if cfg.get("platform", "cpu") == "cpu" and ndev > 1:
        jax.config.update("jax_num_cpu_devices", ndev)
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    proc = _WorkerProc(cfg, role, idx)
    signal.signal(signal.SIGTERM, lambda *_: setattr(proc, "draining", True))
    proc.start()
    proc.serve()
    return 0


if __name__ == "__main__":
    sys.exit(main())
