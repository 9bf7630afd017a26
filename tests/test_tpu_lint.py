"""tpu-lint (paddle_tpu.analysis): per-rule TP/TN fixtures, pragma
suppression, baseline round-trip, the whole-tree CI gate, CLI smoke
(JSON shape + exit codes), and the runtime companions
(assert_no_retrace / tracer-leak detection)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.analysis import (
    RULES, fingerprints, fix_source, format_json, format_sarif,
    lint_paths, lint_project_sources, lint_source, load_baseline,
    preview_diff, profile_of, rules_for, split_findings, write_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(src):
    return [f.rule for f in lint_source(textwrap.dedent(src), path="fix.py")]


# ---------------------------------------------------------------------------
# per-rule fixtures: at least one true positive and one true negative each
# ---------------------------------------------------------------------------

class TestRuleFixtures:
    def test_parse_error_tp(self):
        assert _rules("def f(:\n") == ["PTL000"]

    def test_parse_error_tn(self):
        assert _rules("x = 1\n") == []

    # PTL001 — concretization-in-jit -----------------------------------
    def test_concretization_tp_builtin(self):
        assert _rules("""
            import jax
            @jax.jit
            def f(x):
                return float(x) * 2
        """) == ["PTL001"]

    def test_concretization_tp_item_and_np(self):
        found = _rules("""
            import jax
            import numpy as np
            @jax.jit
            def f(x, y):
                a = np.asarray(x)
                return a + y.item()
        """)
        assert found == ["PTL001", "PTL001"]

    def test_concretization_tn_static_arg(self):
        # `n` is static — int(n) is legal trace-time host python
        assert _rules("""
            import functools
            import jax
            @functools.partial(jax.jit, static_argnames=("n",))
            def f(x, n):
                return x * int(n)
        """) == []

    def test_concretization_tn_outside_jit(self):
        assert _rules("""
            import numpy as np
            def f(x):
                return float(np.asarray(x))
        """) == []

    def test_concretization_in_jit_assignment_wrapper(self):
        # x = jax.jit(fn) marks fn's body traced
        assert _rules("""
            import jax
            def f(x):
                return int(x)
            g = jax.jit(f)
        """) == ["PTL001"]

    # PTL002 — traced-python-branch ------------------------------------
    def test_branch_tp_if(self):
        assert _rules("""
            import jax
            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """) == ["PTL002"]

    def test_branch_tp_while(self):
        assert _rules("""
            import jax
            @jax.jit
            def f(x):
                while x < 10:
                    x = x + 1
                return x
        """) == ["PTL002"]

    def test_branch_tn_static_and_guards(self):
        # static arg, shape access, isinstance guard, `is None`: all fine
        assert _rules("""
            import functools
            import jax
            @functools.partial(jax.jit, static_argnames=("mode",))
            def f(x, y, mode):
                if mode == "fast":
                    x = x * 2
                if x.shape[0] > 1:
                    x = x + 1
                if isinstance(y, jax.core.Tracer):
                    x = x + 0
                if y is None:
                    return x
                return x + y
        """) == []

    # PTL003 — retrace-risk --------------------------------------------
    def test_retrace_tp_unhashable_static(self):
        assert _rules("""
            import functools
            import jax
            @functools.partial(jax.jit, static_argnums=(1,))
            def f(x, cfg):
                return x
            def g(x):
                return f(x, [1, 2])
        """) == ["PTL003"]

    def test_retrace_tp_loop_var_static(self):
        assert _rules("""
            import functools
            import jax
            @functools.partial(jax.jit, static_argnames=("k",))
            def f(x, k):
                return x
            def g(x):
                for k in range(8):
                    x = f(x, k)
                return x
        """) == ["PTL003"]

    def test_retrace_tp_inline_list_dynamic(self):
        assert _rules("""
            import jax
            @jax.jit
            def f(xs):
                return xs
            def g(a, b):
                return f([a, b])
        """) == ["PTL003"]

    def test_retrace_tp_mesh_in_static_position(self):
        # a Mesh built PER CALL in a static slot re-keys every dispatch
        assert _rules("""
            import functools
            import jax
            from jax.sharding import Mesh
            @functools.partial(jax.jit, static_argnums=(1,))
            def f(x, mesh):
                return x
            def g(x, devs):
                return f(x, Mesh(devs, ("mp",)))
        """) == ["PTL003"]

    def test_retrace_tp_named_sharding_static_kwarg(self):
        assert _rules("""
            import functools
            import jax
            @functools.partial(jax.jit, static_argnames=("sh",))
            def f(x, sh=None):
                return x
            def g(x, mesh, spec):
                return f(x, sh=jax.sharding.NamedSharding(mesh, spec))
        """) == ["PTL003"]

    def test_retrace_tn(self):
        # tuple static, array variable dynamic: no churn
        assert _rules("""
            import functools
            import jax
            @functools.partial(jax.jit, static_argnums=(1,))
            def f(x, cfg):
                return x
            def g(x):
                return f(x, (1, 2))
        """) == []

    def test_retrace_tn_hoisted_mesh(self):
        # the sanctioned pattern: ONE Mesh instance, reused per call —
        # and an inline Mesh in a DYNAMIC position is someone else's
        # problem (jax rejects it), not cache churn
        assert _rules("""
            import functools
            import jax
            from jax.sharding import Mesh
            MESH = Mesh(DEVS, ("mp",))
            @functools.partial(jax.jit, static_argnums=(1,))
            def f(x, mesh):
                return x
            def g(x):
                return f(x, MESH)
        """) == []

    # PTL004 — host-sync-in-step-loop ----------------------------------
    def test_host_sync_tp(self):
        assert _rules("""
            import numpy as np
            def serve(engine, xs):
                out = []
                for x in xs:
                    y = engine.step(x)
                    out.append(np.asarray(y))
                return out
        """) == ["PTL004"]

    def test_host_sync_tp_block_until_ready(self):
        assert _rules("""
            def train(step_fn, batches):
                for b in batches:
                    loss = step_fn(b)
                    loss.block_until_ready()
        """) == ["PTL004"]

    def test_host_sync_tn_outside_loop(self):
        assert _rules("""
            import numpy as np
            def serve(engine, xs):
                for x in xs:
                    y = engine.step(x)
                return np.asarray(y)
        """) == []

    def test_host_sync_tn_no_step_in_loop(self):
        assert _rules("""
            import numpy as np
            def f(xs):
                return [np.asarray(x) for x in xs]
            def g(xs):
                out = []
                for x in xs:
                    out.append(np.asarray(x))
                return out
        """) == []

    def test_host_sync_tp_numpy_method(self):
        # paddle-tensor readback: .numpy() blocks like .item()
        assert _rules("""
            def train(step_fn, batches):
                for b in batches:
                    loss = step_fn(b)
                    print(loss.numpy())
        """) == ["PTL004"]

    def test_host_sync_tn_sanctioned_host_fetch(self):
        # the deferred-readback helper (serving/engine.py) is the
        # SANCTIONED sync point of the pipelined drain: routed calls are
        # never recorded, a raw np.asarray next to it still is
        assert _rules("""
            import numpy as np
            from paddle_tpu.serving.engine import _host_fetch
            def drain(engine, xs):
                out = []
                for x in xs:
                    y = engine.step(x)
                    (t,) = _host_fetch(y)
                    out.append(t)
                return out
        """) == []

    def test_host_sync_tp_raw_asarray_beside_sanctioned(self):
        assert _rules("""
            import numpy as np
            from paddle_tpu.serving.engine import _host_fetch
            def drain(engine, xs):
                out = []
                for x in xs:
                    y = engine.step(x)
                    (t,) = _host_fetch(y)
                    out.append(np.asarray(y))
                return out
        """) == ["PTL004"]

    def test_host_sync_tp_numpy_aliased_to_host_fetch(self):
        # the exemption follows the RESOLVED import: smuggling the raw
        # primitive in under the helper's name earns no sanction
        assert _rules("""
            from numpy import asarray as host_fetch
            def drain(engine, xs):
                out = []
                for x in xs:
                    y = engine.step(x)
                    out.append(host_fetch(y))
                return out
        """) == ["PTL004"]

    def test_host_sync_tn_local_host_fetch_helper(self):
        # a locally defined funneling helper is the same design pattern as
        # the engine's — sanctioned through its (bare) resolved name
        assert _rules("""
            import numpy as np
            def _host_fetch(*arrays):
                return [np.asarray(a) for a in arrays]
            def drain(engine, xs):
                out = []
                for x in xs:
                    y = engine.step(x)
                    (t,) = _host_fetch(y)
                    out.append(t)
                return out
        """) == []

    def test_host_sync_tp_prefill_chunk_loop(self):
        # the serving engine's chunked-prefill dispatch loop is a step
        # loop: each serving_prefill_chunk dispatch is per-iteration
        # compiled device work, so a raw sync inside it serializes the
        # pipeline exactly like one inside a decode-step loop
        assert _rules("""
            import numpy as np
            def spend(engine, slots):
                for s in slots:
                    first = engine.serving_prefill_chunk(s)
                    engine.cur[s] = int(np.asarray(first)[0])
        """) == ["PTL004"]

    def test_host_sync_tn_prefill_chunk_loop_sanctioned(self):
        # the budgeted chunk loop itself is clean when the only readback
        # funnels through the sanctioned drain helper AFTER the loop
        assert _rules("""
            import numpy as np
            from paddle_tpu.serving.engine import _host_fetch
            def spend(engine, slots):
                firsts = []
                for s in slots:
                    firsts.append(engine.serving_prefill_chunk(s))
                return _host_fetch(*firsts)
        """) == []

    # PTL008 — blocking-wait-in-step-loop ------------------------------
    def test_wait_tp_sleep_in_step_loop(self):
        assert _rules("""
            import time
            def serve(engine, xs):
                for x in xs:
                    engine.step(x)
                    time.sleep(0.01)
        """) == ["PTL008"]

    def test_wait_tn_sleep_without_step(self):
        assert _rules("""
            import time
            def poll(q):
                while q.empty():
                    time.sleep(0.01)
        """) == []

    def test_wait_tn_sanctioned_backoff(self):
        # the bounded-retry backoff helper (serving/engine.py) is the one
        # legitimate wait on a step loop — routed calls are not recorded
        assert _rules("""
            from paddle_tpu.serving.engine import _backoff_sleep
            def serve(engine, xs):
                for x in xs:
                    engine.step(x)
                    _backoff_sleep(0.01)
        """) == []

    def test_wait_tp_sleep_aliased_to_backoff(self):
        # like PTL004's host_fetch sanction, the exemption follows the
        # RESOLVED import — aliasing time.sleep earns nothing
        assert _rules("""
            from time import sleep as _backoff_sleep
            def serve(engine, xs):
                for x in xs:
                    engine.step(x)
                    _backoff_sleep(0.01)
        """) == ["PTL008"]

    def test_wait_tp_nested_loop_propagates(self):
        # a sleep in an inner non-step loop still stalls the enclosing
        # step loop every iteration
        assert _rules("""
            import time
            def serve(engine, xs):
                for x in xs:
                    engine.step(x)
                    for _ in range(3):
                        time.sleep(0.01)
        """) == ["PTL008"]

    # PTL009 — per-request-metric-label --------------------------------
    def test_labels_tp_rid_in_step_loop(self):
        assert _rules("""
            def serve(engine, reqs, m):
                for r in reqs:
                    engine.step(r)
                    m.labels(rid=r.rid).inc()
        """) == ["PTL009"]

    def test_labels_tp_fstring_wrapped_rid(self):
        # str()/f-string wrapping does not hide the identifier
        assert _rules("""
            def serve(engine, reqs, m):
                for r in reqs:
                    engine.step(r)
                    m.labels(request=f"req-{r.request_id}").observe(1.0)
        """) == ["PTL009"]

    def test_labels_tp_uuid_call(self):
        assert _rules("""
            import uuid
            def serve(engine, xs, m):
                for x in xs:
                    engine.step(x)
                    m.labels(trace=str(uuid.uuid4())).inc()
        """) == ["PTL009"]

    def test_labels_tp_nested_loop_propagates(self):
        # minted in an inner non-step loop, still per-iteration of the
        # enclosing step loop
        assert _rules("""
            def serve(engine, batches, m):
                for b in batches:
                    engine.step(b)
                    for r in b:
                        m.labels(rid=r.rid).inc()
        """) == ["PTL009"]

    def test_labels_tn_bounded_dimensions(self):
        # policy/bucket/status/slo_class are bounded label sets — the
        # EngineMetrics idiom stays clean
        assert _rules("""
            def serve(engine, reqs, m):
                for r in reqs:
                    engine.step(r)
                    m.labels(policy="continuous", bucket=r.bucket).inc()
                    m.labels(slo_class=r.slo_class).observe(0.1)
        """) == []

    def test_labels_tn_rid_outside_step_loop(self):
        # a rid label in a loop that never dispatches a step is someone
        # else's problem (offline analysis, test code)
        assert _rules("""
            def summarize(reqs, m):
                for r in reqs:
                    m.labels(rid=r.rid).inc()
        """) == []

    # PTL010 — host-list-step-operand ----------------------------------
    def test_host_list_tp_bare_comprehension(self):
        # a per-request block-index list: its length tracks the request's
        # mapped chain, so the operand shape churns every admission
        assert _rules("""
            def serve(engine, reqs):
                for r in reqs:
                    engine.decode_step(r.x, [b for b in r.blocks])
        """) == ["PTL010"]

    def test_host_list_tp_jnp_wrapped(self):
        # wrapping at the call site doesn't help — the array inherits
        # the list's ragged length
        assert _rules("""
            import jax.numpy as jnp
            def serve(engine, reqs):
                for r in reqs:
                    engine.decode_step(
                        r.x, jnp.asarray([b for b in r.blocks]))
        """) == ["PTL010"]

    def test_host_list_tp_np_wrapped_also_syncs(self):
        # np.asarray([...]) fed to the step is both a host sync (PTL004)
        # and a ragged operand (PTL010) — both fire, ordered by column
        # (the step call encloses the asarray call)
        assert _rules("""
            import numpy as np
            def serve(engine, reqs):
                for r in reqs:
                    engine.decode_step(r.x, np.asarray([0, 1]))
        """) == ["PTL010", "PTL004"]

    def test_host_list_tn_fixed_shape_table(self):
        # the sanctioned paged-KV idiom: the [B, W] sentinel-padded
        # ndarray mirror shipped whole — no list child, no finding (and
        # jnp.asarray is not a host sync, so PTL004 stays quiet too)
        assert _rules("""
            import jax.numpy as jnp
            def serve(engine, kv, reqs):
                for r in reqs:
                    engine.decode_step(r.x, jnp.asarray(kv.block_tables))
        """) == []

    def test_host_list_tn_outside_step_loop(self):
        # a one-off warmup call with a literal operand is not the hazard
        assert _rules("""
            def warmup(engine, x):
                engine.decode_step(x, [0, 1])
        """) == []

    # PTL011 — implicit-dtype-promotion-in-compiled-step ---------------
    def test_promotion_tp_np_float64(self):
        # a strongly-typed 64-bit scalar outranks the traced operand on
        # the promotion lattice — the int8/bf16 hot loop silently upcasts
        assert _rules("""
            import jax
            import numpy as np
            @jax.jit
            def step(q):
                return q * np.float64(0.5)
        """) == ["PTL011"]

    def test_promotion_tp_np_double_aliased_reversed(self):
        # resolved through the import alias; operand order and a unary
        # sign don't hide the scalar
        assert _rules("""
            import jax
            import numpy as onp
            @jax.jit
            def step(q):
                return -onp.double(2.0) + q
        """) == ["PTL011"]

    def test_promotion_tp_float_pinned_literal(self):
        # float(127.0) concretizes the literal — the fix is the bare
        # literal, which JAX keeps weakly typed
        assert _rules("""
            import jax
            @jax.jit
            def dequant(q):
                return q / float(127.0)
        """) == ["PTL011"]

    def test_promotion_tn_bare_literal(self):
        # a bare python literal stays weakly typed: the traced operand's
        # precision wins, so this is the sanctioned spelling
        assert _rules("""
            import jax
            @jax.jit
            def step(q):
                return q * 0.5
        """) == []

    def test_promotion_tn_outside_jit(self):
        # host-side math is free to use concrete 64-bit scalars
        assert _rules("""
            import numpy as np
            def host(x):
                return x * np.float64(0.5)
        """) == []

    def test_promotion_tn_untraced_operand(self):
        # combined with a trace-time python constant, not a traced value
        assert _rules("""
            import jax
            import numpy as np
            @jax.jit
            def step(q):
                d = 4
                return q[0] + (d * np.float64(0.5) - d)
        """) == []

    def test_promotion_tn_dtype_matched_constant(self):
        # the hinted fix: build the constant in the operand's own dtype
        assert _rules("""
            import jax
            import jax.numpy as jnp
            @jax.jit
            def step(q):
                return q * jnp.asarray(0.5, q.dtype)
        """) == []

    # PTL005 — impure-jit-body -----------------------------------------
    def test_impure_tp_time_and_nprandom(self):
        assert _rules("""
            import time
            import jax
            import numpy as np
            @jax.jit
            def f(x):
                t = time.time()
                return x + np.random.randint(0, 3) + t
        """) == ["PTL005", "PTL005"]

    def test_impure_tp_stdlib_random(self):
        assert _rules("""
            import random
            import jax
            @jax.jit
            def f(x):
                return x * random.random()
        """) == ["PTL005"]

    def test_impure_tp_self_mutation(self):
        assert _rules("""
            import jax
            class M:
                def __init__(self):
                    self._j = jax.jit(self._fn)
                def _fn(self, x):
                    self.cache = x
                    return x
        """) == ["PTL005"]

    def test_impure_tn_keyed_prng_and_host_time(self):
        assert _rules("""
            import time
            import jax
            @jax.jit
            def f(x, key):
                return x + jax.random.uniform(key, x.shape)
            def host():
                return time.time()
        """) == []

    # PTL006 — mutable-default-arg -------------------------------------
    def test_mutable_default_tp(self):
        assert _rules("def f(x, axis=[0, 1]):\n    return x\n") == ["PTL006"]

    def test_mutable_default_tn(self):
        assert _rules("def f(x, axis=(0, 1), d=None):\n    return x\n") == []

    # PTL007 — bare-except ---------------------------------------------
    def test_bare_except_tp(self):
        assert _rules("""
            def f():
                try:
                    return 1
                except:
                    return 0
        """) == ["PTL007"]

    def test_bare_except_tn(self):
        assert _rules("""
            def f():
                try:
                    return 1
                except Exception:
                    return 0
        """) == []

    # PTL012 — interpret-mode-pallas-call ------------------------------
    def test_interpret_tp_literal(self):
        # literal interpret=True outside tests ships a host-emulated
        # kernel; resolved through the module alias
        assert _rules("""
            from jax.experimental import pallas as pl
            def launch(kernel, grid):
                return pl.pallas_call(kernel, grid=grid, interpret=True)
        """) == ["PTL012"]

    def test_interpret_tp_from_import_and_partial(self):
        # a from-import alias and a functools.partial wrapping both
        # resolve to pallas_call
        assert _rules("""
            import functools
            from jax.experimental.pallas import pallas_call as launch_k
            def a(kernel):
                return launch_k(kernel, interpret=True)
            def b(kernel):
                return functools.partial(launch_k, kernel,
                                         interpret=True)()
        """) == ["PTL012", "PTL012"]

    def test_interpret_tn_computed_value(self):
        # the sanctioned CPU-fallback idiom: interpret gated on the
        # backend (a computed value, not a literal)
        assert _rules("""
            import jax
            from jax.experimental import pallas as pl
            def launch(kernel, grid, interpret=None):
                if interpret is None:
                    interpret = jax.default_backend() != "tpu"
                return pl.pallas_call(kernel, grid=grid,
                                      interpret=interpret)
        """) == []

    def test_interpret_tn_test_file(self):
        # test files pin the emulated path on purpose — both a tests/
        # path component and a test_ basename are exempt
        src = textwrap.dedent("""
            from jax.experimental import pallas as pl
            def launch(kernel):
                return pl.pallas_call(kernel, interpret=True)
        """)
        for path in ("tests/helpers.py", "test_kernels.py"):
            assert [f.rule for f in lint_source(src, path=path)] == []

    # PTL013 — blocking-call-in-async-handler --------------------------
    def test_async_blocking_tp_time_sleep(self):
        # time.sleep on the event-loop thread stalls every coroutine —
        # the direct spelling and a from-import alias both resolve
        assert _rules("""
            import time
            async def handler(writer):
                time.sleep(0.1)
        """) == ["PTL013"]
        assert _rules("""
            from time import sleep as snooze
            async def handler(writer):
                snooze(0.1)
        """) == ["PTL013"]

    def test_async_blocking_tp_host_fetch(self):
        # the engine's sanctioned device sync is SANCTIONED for host
        # step loops (PTL004) — inside an async handler the deliberate
        # block is exactly the offense
        assert _rules("""
            from paddle_tpu.serving.engine import _host_fetch
            async def handler(arr):
                vals = _host_fetch(arr)
                return vals
        """) == ["PTL013"]

    def test_async_blocking_tp_socket(self):
        # blocking socket-module entry points and blocking socket
        # methods; asyncio replaces both with streams / loop.sock_*
        assert _rules("""
            import socket
            async def handler(host):
                conn = socket.create_connection((host, 80))
                conn.sendall(b"ping")
                return conn.recv(1024)
        """) == ["PTL013", "PTL013", "PTL013"]

    def test_async_blocking_tn_sync_def(self):
        # the same calls in a plain def are PTL004/PTL008's domain (and
        # clean outside step loops) — PTL013 never fires off the loop
        assert _rules("""
            import time, socket
            def worker(host):
                time.sleep(0.1)
                return socket.create_connection((host, 80))
        """) == []

    def test_async_blocking_tn_nested_sync_def(self):
        # a nested plain def inside an async handler runs wherever it's
        # CALLED (executor / driver thread) — the innermost def's
        # asyncness decides, not any enclosing one
        assert _rules("""
            import time
            async def handler(loop):
                def blocking_probe():
                    time.sleep(0.1)
                    return 1
                return await loop.run_in_executor(None, blocking_probe)
        """) == []

    def test_async_blocking_tn_awaited_idioms(self):
        # the sanctioned spellings: asyncio.sleep, asyncio streams, and
        # a smuggled alias of asyncio.sleep under the name time.sleep
        # would not resolve to time.sleep
        assert _rules("""
            import asyncio
            async def handler(reader, writer):
                await asyncio.sleep(0.1)
                data = await reader.readexactly(4)
                writer.write(data)
                await writer.drain()
        """) == []

    # rule filtering ----------------------------------------------------
    def test_rules_filter(self):
        src = textwrap.dedent("""
            import jax
            @jax.jit
            def f(x, axis=[0]):
                return float(x)
        """)
        assert [f.rule for f in lint_source(src, rules=["PTL006"])] \
            == ["PTL006"]
        assert [f.rule for f in lint_source(src)] == ["PTL006", "PTL001"]


# ---------------------------------------------------------------------------
# pragmas
# ---------------------------------------------------------------------------

class TestPragmas:
    SRC = textwrap.dedent("""
        import jax
        @jax.jit
        def f(x):
            return float(x){pragma}
    """)

    def test_bare_ignore(self):
        src = self.SRC.format(pragma="  # tpu-lint: ignore")
        assert lint_source(src) == []

    def test_scoped_ignore(self):
        src = self.SRC.format(pragma="  # tpu-lint: ignore[PTL001]")
        assert lint_source(src) == []

    def test_non_matching_id_not_suppressed(self):
        src = self.SRC.format(pragma="  # tpu-lint: ignore[PTL007]")
        assert [f.rule for f in lint_source(src)] == ["PTL001"]

    def test_multiple_ids(self):
        src = self.SRC.format(pragma="  # tpu-lint: ignore[PTL007, PTL001]")
        assert lint_source(src) == []


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------

DIRTY = "import jax\n\n@jax.jit\ndef f(x):\n    return float(x)\n"


class TestBaseline:
    def test_round_trip(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(DIRTY)
        findings = lint_paths([str(mod)])
        assert [f.rule for f in findings] == ["PTL001"]

        bl = tmp_path / "baseline.json"
        payload = write_baseline(str(bl), findings)
        assert payload["count"] == 1
        fps = load_baseline(str(bl))
        assert fps == set(payload["findings"])

        new, old = split_findings(findings, fps)
        assert new == [] and len(old) == 1

    def test_baseline_survives_line_shift(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(DIRTY)
        findings = lint_paths([str(mod)])
        bl = tmp_path / "baseline.json"
        write_baseline(str(bl), findings)
        # unrelated edit above the finding: fingerprint (line-text based)
        # still matches
        mod.write_text("# a new comment\n# another\n" + DIRTY)
        shifted = lint_paths([str(mod)])
        assert shifted[0].line != findings[0].line
        new, old = split_findings(shifted, load_baseline(str(bl)))
        assert new == [] and len(old) == 1

    def test_new_finding_not_masked(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(DIRTY)
        bl = tmp_path / "baseline.json"
        write_baseline(str(bl), lint_paths([str(mod)]))
        mod.write_text(DIRTY + "\n\ndef g(x, d=[1]):\n    return d\n")
        new, old = split_findings(lint_paths([str(mod)]),
                                  load_baseline(str(bl)))
        assert [f.rule for f in new] == ["PTL006"]
        assert [f.rule for f in old] == ["PTL001"]

    def test_fingerprints_disambiguate_identical_lines(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text("def f(a=[1]):\n    return a\n\n"
                       "def f(a=[1]):\n    return a\n")
        findings = lint_paths([str(mod)])
        assert len(findings) == 2
        assert len(set(fingerprints(findings))) == 2


# ---------------------------------------------------------------------------
# the CI gate: whole paddle_tpu tree must be clean against the baseline
# ---------------------------------------------------------------------------

class TestTreeGate:
    def test_tree_has_no_new_findings(self):
        tree = os.path.join(REPO, "paddle_tpu")
        baseline = os.path.join(REPO, "tpu_lint_baseline.json")
        assert os.path.isfile(baseline), "tpu_lint_baseline.json missing"
        findings = lint_paths([tree])
        new, _ = split_findings(findings, load_baseline(baseline))
        msgs = [f"{f.path}:{f.line}: {f.rule} {f.message}" for f in new]
        assert not new, (
            "new tpu-lint finding(s) — fix them, add a justified "
            "`# tpu-lint: ignore[...]` pragma, or (last resort) regenerate "
            "the baseline with `python -m paddle_tpu.analysis paddle_tpu "
            "--write-baseline`:\n" + "\n".join(msgs))

    def test_every_rule_has_metadata(self):
        for rid, rule in RULES.items():
            assert rule.id == rid and rule.severity in ("error", "warning")
            assert rule.description and rule.hint and rule.name


# ---------------------------------------------------------------------------
# CLI smoke: exit codes + JSON output shape
# ---------------------------------------------------------------------------

def _run_cli(args, cwd=REPO):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", *args],
        capture_output=True, text=True, cwd=cwd, env=env, timeout=240)


class TestCLI:
    def test_json_shape_and_exit_1(self, tmp_path):
        mod = tmp_path / "dirty.py"
        mod.write_text(DIRTY)
        r = _run_cli([str(mod), "--format", "json", "--no-baseline"])
        assert r.returncode == 1, r.stderr
        payload = json.loads(r.stdout)
        assert payload["tool"] == "paddle_tpu.analysis"
        assert payload["summary"]["new"] == 1
        assert payload["summary"]["errors"] == 1
        assert payload["counts_by_rule"] == {"PTL001": 1}
        (entry,) = payload["new"]
        for key in ("rule", "severity", "path", "line", "col", "message",
                    "hint", "fingerprint"):
            assert key in entry
        assert entry["rule"] == "PTL001" and entry["severity"] == "error"

    def test_clean_file_exit_0(self, tmp_path):
        mod = tmp_path / "clean.py"
        mod.write_text("x = 1\n")
        r = _run_cli([str(mod), "--no-baseline"])
        assert r.returncode == 0, r.stderr
        assert "0 new finding(s)" in r.stdout

    def test_usage_errors_exit_2(self, tmp_path):
        r = _run_cli(["--rules", "PTL999", str(tmp_path)])
        assert r.returncode == 2 and "unknown rule" in r.stderr
        r = _run_cli([str(tmp_path / "nope.py")])
        assert r.returncode == 2 and "no such path" in r.stderr

    def test_list_rules(self):
        r = _run_cli(["--list-rules"])
        assert r.returncode == 0
        for rid in RULES:
            assert rid in r.stdout


# ---------------------------------------------------------------------------
# runtime companions
# ---------------------------------------------------------------------------

class TestRuntime:
    def _monitored(self):
        from paddle_tpu.observability.compilecache import CompileCacheMonitor
        from paddle_tpu.observability.metrics import MetricsRegistry

        mon = CompileCacheMonitor("test", registry=MetricsRegistry())

        @jax.jit
        def f(x):
            mon.mark_trace("f")
            return x * 2

        return mon, f

    def test_assert_no_retrace_passes_on_cache_hit(self):
        from paddle_tpu.analysis import assert_no_retrace

        mon, f = self._monitored()
        f(jnp.ones((2,)))  # warmup: first trace happens outside the block
        with assert_no_retrace(mon):
            f(jnp.ones((2,)))
            f(jnp.zeros((2,)))

    def test_assert_no_retrace_raises_on_shape_churn(self):
        from paddle_tpu.analysis import RetraceError, assert_no_retrace

        mon, f = self._monitored()
        f(jnp.ones((2,)))
        with pytest.raises(RetraceError, match=r"test/f: \+1"):
            with assert_no_retrace(mon):
                f(jnp.ones((3,)))  # new shape: retrace

    def test_assert_no_retrace_program_filter(self):
        from paddle_tpu.analysis import assert_no_retrace

        mon, f = self._monitored()
        f(jnp.ones((2,)))
        with assert_no_retrace(mon, programs=("other",)):
            f(jnp.ones((5,)))  # retraces, but `f` is not watched

    def test_tracer_leak_detected(self):
        from paddle_tpu.analysis import TracerLeakError, assert_no_tracer_leak

        sink = []

        def leaky(x):
            sink.append(x)  # retains the tracer beyond the trace
            return x * 2

        with pytest.raises(TracerLeakError, match="outlived the trace"):
            assert_no_tracer_leak(leaky, jnp.ones((2,)))
        sink.clear()

    def test_derived_tracer_leak_detected(self):
        from paddle_tpu.analysis import find_tracer_leaks

        sink = []

        def leaky(x):
            sink.append(x * 2)  # leaks a tracer CREATED during the trace
            return x + 1

        assert find_tracer_leaks(leaky, jnp.ones((3,)))
        sink.clear()

    def test_tracer_leak_clean(self):
        from paddle_tpu.analysis import find_tracer_leaks

        def clean(x):
            return x * 2 + 1

        assert find_tracer_leaks(clean, jnp.ones((2,))) == []

# ---------------------------------------------------------------------------
# v2: interprocedural traced-value propagation
# ---------------------------------------------------------------------------

class TestInterprocedural:
    HELPER_ITEM = textwrap.dedent("""
        import jax

        def helper(v):
            return v.item()

        @jax.jit
        def fwd(x):
            return helper(x)
    """)

    def test_one_hop_flagged_by_v2_not_v1(self):
        # the acceptance fixture: a jitted body calling a helper that
        # concretizes its traced arg — invisible to the v1 single-pass
        # walk, flagged with the call chain by the v2 dataflow pass
        v1 = lint_source(self.HELPER_ITEM, path="m.py",
                         interprocedural=False)
        assert v1 == []
        (f,) = lint_source(self.HELPER_ITEM, path="m.py")
        assert f.rule == "PTL001"
        assert "[traced via fwd -> helper]" in f.message
        assert f.line == 5  # anchored at the offending line in the HELPER

    def test_two_hops(self):
        src = textwrap.dedent("""
            import jax

            def inner(v):
                if v:
                    return 1
                return 0

            def outer(v):
                return inner(v)

            @jax.jit
            def fwd(x):
                return outer(x)
        """)
        (f,) = lint_source(src, path="m.py")
        assert f.rule == "PTL002"
        assert "[traced via fwd -> outer -> inner]" in f.message

    def test_static_arg_not_propagated(self):
        src = textwrap.dedent("""
            import jax
            import functools

            def helper(v):
                return int(v)

            @functools.partial(jax.jit, static_argnames=("n",))
            def fwd(x, n):
                return helper(n) + x
        """)
        assert lint_source(src, path="m.py") == []

    def test_static_attr_laundering_through_call(self):
        # `x.shape[0]` / `params["w"].dtype` are compile-time metadata:
        # passing them to a helper must not mark its param traced
        src = textwrap.dedent("""
            import jax

            def helper(n, dt):
                if dt == "int8":
                    return int(n)
                return n

            @jax.jit
            def fwd(x, params):
                return helper(x.shape[0], params["w"].dtype)
        """)
        assert lint_source(src, path="m.py") == []

    def test_pragma_on_callee_line_suppresses(self):
        src = self.HELPER_ITEM.replace(
            "return v.item()",
            "return v.item()  # tpu-lint: ignore[PTL001]")
        assert lint_source(src, path="m.py") == []

    def test_cross_module_propagation(self):
        files = {
            "pkg/ops.py": textwrap.dedent("""
                def helper(v):
                    return v.item()
            """),
            "pkg/model.py": textwrap.dedent("""
                import jax
                from pkg.ops import helper

                @jax.jit
                def fwd(x):
                    return helper(x)
            """),
        }
        findings = lint_project_sources(files)
        (f,) = [f for f in findings if f.rule == "PTL001"]
        assert f.path == "pkg/ops.py"
        assert "[traced via fwd -> helper]" in f.message

    def test_effect_summary_host_sync(self):
        # PTL004 sees a sync hidden behind a helper, with a witness chain
        src = textwrap.dedent("""
            import numpy as np

            def drain(h):
                return np.asarray(h)

            def serve(step, batches):
                for b in batches:
                    out = step(b)
                    drain(out)
        """)
        (f,) = lint_source(src, path="m.py")
        assert f.rule == "PTL004"
        assert "reaches np.asarray() via drain" in f.message

    def test_step_plus_sync_call_not_charged(self):
        # a callee that BOTH dispatches the step and reads back is a
        # self-contained unit — its caller's loop is not the violation
        src = textwrap.dedent("""
            import numpy as np

            def train_step(b):
                loss = _step(b)
                return np.asarray(loss)

            def fit(batches):
                for b in batches:
                    train_step(b)
        """)
        assert lint_source(src, path="m.py") == []

    def test_outer_loop_sync_amortized_over_inner_steps(self):
        # sync once per epoch around an inner step loop is the pattern
        # PTL004 RECOMMENDS; only the innermost dispatching loop counts
        src = textwrap.dedent("""
            import numpy as np

            def fit(epochs, batches, evaluate):
                for epoch in range(epochs):
                    for b in batches:
                        loss = train_step(b)
                    np.asarray(loss)
        """)
        assert lint_source(src, path="m.py") == []

    def test_builder_name_is_not_a_dispatch(self):
        src = textwrap.dedent("""
            import numpy as np

            def refresh(self):
                build_train_step(self)

            def loop(items):
                for it in items:
                    refresh(it)
                    np.asarray(it)
        """)
        assert lint_source(src, path="m.py") == []


# ---------------------------------------------------------------------------
# PTL014: program-cache-key completeness
# ---------------------------------------------------------------------------

class TestPTL014:
    IMPLS = textwrap.dedent("""
        import functools
        import jax

        def _decode_impl(params, caches, cfg, n_steps, attn_impl):
            return caches

        serving_decode = _mon.wrap("serving_decode", jax.jit(
            _decode_impl,
            static_argnames=("cfg", "n_steps", "attn_impl"),
            donate_argnames=("caches",)))
    """)

    def _factory(self, key_line):
        return textwrap.dedent("""
            from pkg.impls import serving_decode

            _PROGRAMS = {}

            def tp_programs(mesh, cfg, sync_every, attn_impl):
                key = %s
                hit = _PROGRAMS.get(key)
                if hit is not None:
                    return hit

                def run(params, caches):
                    return serving_decode(params, caches, cfg,
                                          n_steps=sync_every,
                                          attn_impl=attn_impl)
                _PROGRAMS[key] = run
                return run
        """) % key_line

    def test_complete_key_clean(self):
        files = {"pkg/impls.py": self.IMPLS,
                 "pkg/factory.py": self._factory(
                     "(mesh, cfg, sync_every, attn_impl)")}
        assert [f for f in lint_project_sources(files)
                if f.rule == "PTL014"] == []

    def test_missing_axis_exactly_one_finding(self):
        # the acceptance proof: drop ONE axis from the key tuple -> one
        # finding naming the knob and both file locations
        files = {"pkg/impls.py": self.IMPLS,
                 "pkg/factory.py": self._factory(
                     "(mesh, cfg, sync_every)")}
        found = [f for f in lint_project_sources(files)
                 if f.rule == "PTL014"]
        assert len(found) == 1
        (f,) = found
        assert f.path == "pkg/factory.py"
        assert "`attn_impl`" in f.message
        assert "pkg/impls.py" in f.message and "pkg/factory.py" in f.message

    def test_renamed_binding_counts(self):
        # `n_steps=sync_every` binds the static through a rename: either
        # the param name or the bound local in the key satisfies the axis
        files = {"pkg/impls.py": self.IMPLS,
                 "pkg/factory.py": self._factory(
                     "(mesh, cfg, n_steps, attn_impl)")}
        found = [f for f in lint_project_sources(files)
                 if f.rule == "PTL014"]
        assert [("sync_every" in f.message or "n_steps" in f.message)
                for f in found] == []

    def test_const_bound_static_is_exempt(self):
        # a knob bound to a literal at the call site cannot vary, so it
        # does not need a key axis
        factory = self._factory("(mesh, cfg, sync_every)").replace(
            "attn_impl=attn_impl", "attn_impl='fused'")
        files = {"pkg/impls.py": self.IMPLS, "pkg/factory.py": factory}
        assert [f for f in lint_project_sources(files)
                if f.rule == "PTL014"] == []

    def test_pragma_suppresses(self):
        factory = self._factory("(mesh, cfg, sync_every)").replace(
            "key = (mesh, cfg, sync_every)",
            "key = (mesh, cfg, sync_every)"
            "  # tpu-lint: ignore[PTL014]")
        files = {"pkg/impls.py": self.IMPLS, "pkg/factory.py": factory}
        assert [f for f in lint_project_sources(files)
                if f.rule == "PTL014"] == []

    # -- static-axis registry: PROGRAM_AXES is the single source of truth

    REGISTRY = textwrap.dedent("""
        PROGRAM_AXES = (
            StaticAxis("attn_impl", None, "which attention kernel"),
            StaticAxis(name="kv_dtype", default=None, doc="KV storage"),
            StaticAxis("tp_overlap", None, "psum segmentation",
                       kind="segments"),
        )
    """)

    IMPLS_PK = textwrap.dedent("""
        import jax

        def _decode_impl(params, caches, cfg, n_steps, program_key):
            return caches

        serving_decode = _mon.wrap("serving_decode", jax.jit(
            _decode_impl,
            static_argnames=("cfg", "n_steps", "program_key"),
            donate_argnames=("caches",)))
    """)

    def _registry_factory(self, params, key_line, call_tail):
        return textwrap.dedent("""
            from pkg.impls import serving_decode

            _PROGRAMS = {}

            def tp_programs(%s):
                key = %s
                hit = _PROGRAMS.get(key)
                if hit is not None:
                    return hit

                def run(params, caches):
                    return serving_decode(params, caches, cfg,
                                          %s)
                _PROGRAMS[key] = run
                return run
        """) % (params, key_line, call_tail)

    def test_registry_program_key_covers_every_axis(self):
        # one `program_key` in the key tuple = the whole registry keyed
        files = {"pkg/program_key.py": self.REGISTRY,
                 "pkg/impls.py": self.IMPLS_PK,
                 "pkg/factory.py": self._registry_factory(
                     "mesh, cfg, sync_every, program_key",
                     "(mesh, cfg, sync_every, program_key)",
                     "n_steps=sync_every, program_key=program_key")}
        assert [f for f in lint_project_sources(files)
                if f.rule == "PTL014"] == []

    def test_registry_subset_one_finding_per_missing_axis(self):
        # hand-threading attn_impl alone: kv_dtype and tp_overlap can
        # never fork the cache entry -> one finding each, naming the
        # axis and the registry location
        files = {"pkg/program_key.py": self.REGISTRY,
                 "pkg/impls.py": self.IMPLS,
                 "pkg/factory.py": self._registry_factory(
                     "mesh, cfg, attn_impl",
                     "(mesh, cfg, attn_impl)",
                     "n_steps=4, attn_impl=attn_impl")}
        found = sorted([f for f in lint_project_sources(files)
                        if f.rule == "PTL014"],
                       key=lambda f: f.message)
        assert len(found) == 2
        assert "`kv_dtype`" in found[0].message
        assert "`tp_overlap`" in found[1].message
        for f in found:
            assert f.path == "pkg/factory.py"
            assert "PROGRAM_AXES" in f.message
            assert "pkg/program_key.py" in f.message

    def test_registry_full_hand_threaded_set_clean(self):
        # every registry axis present by name: complete, if inelegant
        files = {"pkg/program_key.py": self.REGISTRY,
                 "pkg/impls.py": self.IMPLS,
                 "pkg/factory.py": self._registry_factory(
                     "mesh, cfg, attn_impl, kv_dtype, tp_overlap",
                     "(mesh, cfg, attn_impl, kv_dtype, tp_overlap)",
                     "n_steps=4, attn_impl=attn_impl")}
        assert [f for f in lint_project_sources(files)
                if f.rule == "PTL014"] == []

    def test_registry_unrelated_key_not_flagged(self):
        # a cache keyed on NO registry axis (a different subsystem's
        # cache) is outside the registry's jurisdiction
        files = {"pkg/program_key.py": self.REGISTRY,
                 "pkg/impls.py": self.IMPLS,
                 "pkg/factory.py": self._factory(
                     "(mesh, cfg, sync_every, attn_impl)").replace(
                         "attn_impl", "impl_choice")}
        assert [f for f in lint_project_sources(files)
                if f.rule == "PTL014"] == []

    def test_registry_subset_pragma_suppresses(self):
        factory = self._registry_factory(
            "mesh, cfg, attn_impl",
            "(mesh, cfg, attn_impl)  # tpu-lint: ignore[PTL014]",
            "n_steps=4, attn_impl=attn_impl")
        files = {"pkg/program_key.py": self.REGISTRY,
                 "pkg/impls.py": self.IMPLS,
                 "pkg/factory.py": factory}
        assert [f for f in lint_project_sources(files)
                if f.rule == "PTL014"] == []


# ---------------------------------------------------------------------------
# PTL015: unsynchronized shared state in lock-owning classes
# ---------------------------------------------------------------------------

class TestPTL015:
    def test_unlocked_write_tp(self):
        src = textwrap.dedent("""
            import threading

            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._vals = {}

                def add(self, k, v):
                    with self._lock:
                        self._vals[k] = v

                def reset(self):
                    self._vals = {}
        """)
        (f,) = lint_source(src, path="m.py")
        assert f.rule == "PTL015"
        assert "`_vals`" in f.message and "reset" in f.message

    def test_mutator_method_tp(self):
        src = textwrap.dedent("""
            import threading

            class Buf:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []

                def flush(self):
                    with self._lock:
                        out, self._items = self._items, []
                    return out

                def push(self, x):
                    self._items.append(x)
        """)
        (f,) = lint_source(src, path="m.py")
        assert f.rule == "PTL015"
        assert "`_items`" in f.message

    def test_init_and_locked_writes_tn(self):
        src = textwrap.dedent("""
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def inc(self):
                    with self._lock:
                        self._n += 1
        """)
        assert lint_source(src, path="m.py") == []

    def test_unprotected_attr_tn(self):
        # an attr never written under the lock is not in the protected
        # set — no claim about it
        src = textwrap.dedent("""
            import threading

            class Mixed:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._hot = {}
                    self.label = ""

                def put(self, k, v):
                    with self._lock:
                        self._hot[k] = v

                def rename(self, s):
                    self.label = s
        """)
        assert lint_source(src, path="m.py") == []

    def test_lockless_class_tn(self):
        src = textwrap.dedent("""
            class Plain:
                def __init__(self):
                    self._vals = {}

                def reset(self):
                    self._vals = {}
        """)
        assert lint_source(src, path="m.py") == []

    def test_pragma_suppresses(self):
        src = textwrap.dedent("""
            import threading

            class Registry:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._vals = {}

                def add(self, k, v):
                    with self._lock:
                        self._vals[k] = v

                def reset_unshared(self):
                    self._vals = {}  # tpu-lint: ignore[PTL015]
        """)
        assert lint_source(src, path="m.py") == []


# ---------------------------------------------------------------------------
# PTL016: donated-buffer reuse
# ---------------------------------------------------------------------------

class TestPTL016:
    def test_read_after_donation_tp(self):
        src = textwrap.dedent("""
            import jax

            def _impl(params, caches):
                return caches

            step = jax.jit(_impl, donate_argnames=("caches",))

            def drive(params, caches):
                out = step(params, caches)
                return caches.shape
        """)
        (f,) = lint_source(src, path="m.py")
        assert f.rule == "PTL016"
        assert "`caches`" in f.message and "step" in f.message

    def test_donate_argnums_kwarg_tp(self):
        src = textwrap.dedent("""
            import jax

            def _impl(params, caches):
                return caches

            step = jax.jit(_impl, donate_argnums=(1,))

            def drive(params, caches):
                out = step(params, caches)
                return len(caches)
        """)
        assert [f.rule for f in lint_source(src, path="m.py")] == ["PTL016"]

    def test_rebind_through_call_tn(self):
        # the serving idiom: the donating call's own statement rebinds
        # the name, so every later read sees the fresh buffer
        src = textwrap.dedent("""
            import jax

            def _impl(params, caches):
                return caches

            step = jax.jit(_impl, donate_argnames=("caches",))

            def drive(params, caches):
                caches = step(params, caches)
                return caches
        """)
        assert lint_source(src, path="m.py") == []

    def test_rebind_before_read_tn(self):
        src = textwrap.dedent("""
            import jax

            def _impl(params, caches):
                return caches

            step = jax.jit(_impl, donate_argnames=("caches",))

            def drive(params, caches, fresh):
                out = step(params, caches)
                caches = fresh
                return caches
        """)
        assert lint_source(src, path="m.py") == []

    def test_non_donated_arg_tn(self):
        src = textwrap.dedent("""
            import jax

            def _impl(params, caches):
                return caches

            step = jax.jit(_impl, donate_argnames=("caches",))

            def drive(params, caches):
                out = step(params, caches)
                return params
        """)
        assert lint_source(src, path="m.py") == []

    def test_pragma_suppresses(self):
        src = textwrap.dedent("""
            import jax

            def _impl(params, caches):
                return caches

            step = jax.jit(_impl, donate_argnames=("caches",))

            def drive(params, caches):
                out = step(params, caches)
                return caches.shape  # tpu-lint: ignore[PTL016]
        """)
        assert lint_source(src, path="m.py") == []


# ---------------------------------------------------------------------------
# PTL017: blocking KV transfer in a step-dispatch loop
# ---------------------------------------------------------------------------

class TestPTL017:
    def test_transport_send_in_step_loop_tp(self):
        src = textwrap.dedent("""
            def drive(transport, reqs, params, caches):
                for r in reqs:
                    out = decode_step(params, r)
                    transport.send(r.rid, caches)
        """)
        (f,) = lint_source(src, path="m.py")
        assert f.rule == "PTL017"
        assert ".send()" in f.message and "kv_transfer" in f.message

    def test_transport_recv_of_chain_tp(self):
        src = textwrap.dedent("""
            def drive(transport, handles, params):
                for h in handles:
                    leaves = transport.recv(chain_handle(h))
                    out = decode_step(params, leaves)
        """)
        assert [f.rule for f in lint_source(src, path="m.py")] \
            == ["PTL017"]

    def test_device_get_of_cache_leaves_tp(self):
        # a raw device_get of cache leaves is BOTH the generic host sync
        # (PTL004) and a blocking KV transfer (PTL017) — the second
        # finding names the migration-specific fix
        src = textwrap.dedent("""
            import jax

            def drive(reqs, params, kv_caches):
                for r in reqs:
                    out = decode_step(params, r)
                    host = jax.device_get(kv_caches)
        """)
        assert [f.rule for f in lint_source(src, path="m.py")] \
            == ["PTL004", "PTL017"]

    def test_outer_loop_propagates_tp(self):
        # transfer in an inner non-step loop still serializes the outer
        # step loop — same propagation as PTL004 syncs
        src = textwrap.dedent("""
            def drive(transport, waves, params, caches):
                for wave in waves:
                    out = decode_step(params, wave)
                    for r in wave:
                        transport.send(r, caches)
        """)
        assert [f.rule for f in lint_source(src, path="m.py")] \
            == ["PTL017"]

    def test_socket_recv_not_kv_tn(self):
        # a socket .recv() in a step loop moves no KV leaves — it is
        # PTL008/PTL013's territory, not a migration anti-pattern
        src = textwrap.dedent("""
            def drive(sock, reqs, params):
                for r in reqs:
                    out = decode_step(params, r)
                    data = sock.recv(4096)
        """)
        assert lint_source(src, path="m.py") == []

    def test_no_step_dispatch_tn(self):
        # the coordinator pump: transfers in a loop with NO step
        # dispatch are the sanctioned staging pattern
        src = textwrap.dedent("""
            def pump(transport, tickets, caches):
                for t in tickets:
                    leaves = transport.recv(t.handle)
                    caches.append(leaves)
        """)
        assert lint_source(src, path="m.py") == []

    def test_sanctioned_helper_tn(self):
        src = textwrap.dedent("""
            def drive(reqs, params, caches, kv_transfer):
                for r in reqs:
                    out = decode_step(params, r)
                    kv_transfer(r, caches)
        """)
        assert lint_source(src, path="m.py") == []

    def test_aliased_primitive_not_sanctioned_tp(self):
        # sanction follows the RESOLVED name: importing a raw sync
        # primitive as `kv_transfer` does not launder the transfer
        src = textwrap.dedent("""
            from jax import device_get as kv_transfer

            def drive(reqs, params, caches):
                for r in reqs:
                    out = decode_step(params, r)
                    host = kv_transfer(caches)
        """)
        assert "PTL017" in [f.rule for f in lint_source(src, path="m.py")]

    def test_pragma_suppresses(self):
        src = textwrap.dedent("""
            def drive(transport, reqs, params, caches):
                for r in reqs:
                    out = decode_step(params, r)
                    transport.send(r.rid, caches)  # tpu-lint: ignore[PTL017]
        """)
        assert lint_source(src, path="m.py") == []

    def test_kv_transfer_send_recv_sanctioned_tn(self):
        # the SocketTransport seam (serving/transport.py): the worker
        # pump calls kv_transfer_recv / the background streamer calls
        # kv_transfer_send inside loops that also dispatch — both ride
        # the sanctioned-name list
        src = textwrap.dedent("""
            def pump(kvx, params, reqs, caches):
                for r in reqs:
                    out = decode_step(params, r)
                    for entry in kvx.kv_transfer_recv():
                        caches.append(entry)
                    kvx.kv_transfer_send(r.rid, caches)
        """)
        assert lint_source(src, path="m.py") == []

    def test_aliased_socket_recv_not_sanctioned_tp(self):
        # resolved-name semantics again: importing a raw transfer as
        # `kv_transfer_recv` does not launder it — the tail of the
        # RESOLVED name (device_get) is what the sanction list sees
        src = textwrap.dedent("""
            from jax import device_get as kv_transfer_recv

            def drive(reqs, params, caches):
                for r in reqs:
                    out = decode_step(params, r)
                    host = kv_transfer_recv(caches)
        """)
        assert "PTL017" in [f.rule for f in lint_source(src, path="m.py")]


# ---------------------------------------------------------------------------
# PTL018 — lock-order inversion (interprocedural lock-acquisition graph)
# ---------------------------------------------------------------------------

class TestPTL018:
    def test_nested_with_inversion_tp(self):
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def f(self):
                    with self._a:
                        with self._b:
                            pass

                def g(self):
                    with self._b:
                        with self._a:
                            pass
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL018"
        # BOTH chains printed, each with file:line evidence
        assert "C._a" in f.message and "C._b" in f.message
        assert "C.f" in f.message and "C.g" in f.message
        assert f.message.count("fix.py:") == 2

    def test_consistent_order_tn(self):
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def f(self):
                    with self._a:
                        with self._b:
                            pass

                def g(self):
                    with self._a:
                        with self._b:
                            pass
        """)
        assert lint_source(src, path="fix.py") == []

    def test_multi_item_with_inversion_tp(self):
        # `with a, b:` acquires left-to-right — inverted against `with b, a:`
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def f(self):
                    with self._a, self._b:
                        pass

                def g(self):
                    with self._b, self._a:
                        pass
        """)
        assert [f.rule for f in lint_source(src, path="fix.py")] \
            == ["PTL018"]

    def test_via_call_inversion_tp(self):
        # one side of the inversion is only reachable through a resolved
        # call — the chain names every hop
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self.a_lock = threading.Lock()
                    self.b_lock = threading.Lock()

                def _grab(self):
                    with self.b_lock:
                        pass

                def f(self):
                    with self.a_lock:
                        self._grab()

                def g(self):
                    with self.b_lock:
                        with self.a_lock:
                            pass
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL018"
        assert "C.f -> C._grab" in f.message

    def test_lock_passed_as_argument_tp(self):
        # a lock handed to a helper as a parameter still builds edges in
        # the caller's identity space
        src = textwrap.dedent("""
            import threading

            def locked_update(lock, items):
                with lock:
                    items.append(1)

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def f(self, items):
                    with self._a:
                        locked_update(self._b, items)

                def g(self):
                    with self._b:
                        with self._a:
                            pass
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL018"
        assert "locked_update" in f.message

    def test_alias_reacquire_not_inversion_tn(self):
        # `lk = self._a` resolves to the SAME lock: a nested re-acquire
        # is RLock territory, not an ordering edge
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._a = threading.RLock()

                def f(self):
                    lk = self._a
                    with self._a:
                        with lk:
                            pass
        """)
        assert lint_source(src, path="fix.py") == []

    def test_cross_module_inversion_tp(self):
        # the two halves of the inversion live in different modules;
        # only the project-level join can see the cycle
        files = {
            "pkg/state.py": textwrap.dedent("""
                import threading

                A_LOCK = threading.Lock()
                B_LOCK = threading.Lock()

                def forward(items):
                    with A_LOCK:
                        with B_LOCK:
                            items.append(1)
            """),
            "pkg/drain.py": textwrap.dedent("""
                from pkg.state import A_LOCK, B_LOCK

                def backward(items):
                    with B_LOCK:
                        with A_LOCK:
                            items.pop()
            """),
        }
        found = [f for f in lint_project_sources(files)
                 if f.rule == "PTL018"]
        assert len(found) == 1
        assert "forward" in found[0].message
        assert "backward" in found[0].message

    def test_pragma_suppresses(self):
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def f(self):
                    with self._a:
                        with self._b:  # tpu-lint: ignore[PTL018]
                            pass

                def g(self):
                    with self._b:
                        with self._a:
                            pass
        """)
        assert lint_source(src, path="fix.py") == []


# ---------------------------------------------------------------------------
# PTL019 — blocking call while holding a lock
# ---------------------------------------------------------------------------

class TestPTL019:
    LOCKED = textwrap.dedent("""
        import threading
        import time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def f(self):
                with self._lock:
                    time.sleep(0.1)
    """)

    def test_sleep_under_lock_tp(self):
        (f,) = lint_source(self.LOCKED, path="fix.py")
        assert f.rule == "PTL019"
        assert "time.sleep" in f.message and "C._lock" in f.message

    def test_socket_recv_under_lock_tp(self):
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def f(self, sock):
                    with self._lock:
                        return sock.recv(4096)
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL019" and ".recv()" in f.message

    def test_queue_get_no_timeout_under_lock_tp(self):
        src = textwrap.dedent("""
            import queue
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()

                def f(self):
                    with self._lock:
                        return self._q.get()
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL019" and "without timeout" in f.message

    def test_queue_get_with_timeout_tn(self):
        src = textwrap.dedent("""
            import queue
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()

                def f(self):
                    with self._lock:
                        return self._q.get(timeout=0.5)
        """)
        assert lint_source(src, path="fix.py") == []

    def test_join_under_lock_tp(self):
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._t = threading.Thread(target=print, daemon=True)

                def f(self):
                    with self._lock:
                        self._t.join()
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL019" and ".join()" in f.message

    def test_condition_wait_tn(self):
        # Condition.wait RELEASES the lock while blocked — the
        # sanctioned producer/consumer handoff, never flagged
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._cv = threading.Condition()

                def f(self):
                    with self._cv:
                        while not self.ready:
                            self._cv.wait()
        """)
        assert lint_source(src, path="fix.py") == []

    def test_blocking_outside_lock_tn(self):
        src = textwrap.dedent("""
            import threading
            import time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def f(self):
                    with self._lock:
                        x = 1
                    time.sleep(0.1)
        """)
        assert lint_source(src, path="fix.py") == []

    def test_propagated_through_helper_tp(self):
        # the blocking call hides behind a resolved helper: the finding
        # lands at the call site with the witness chain and the reached
        # location
        src = textwrap.dedent("""
            import threading
            import time

            def slow_flush(items):
                time.sleep(0.5)
                return items

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def f(self, items):
                    with self._lock:
                        return slow_flush(items)
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL019"
        assert "[via C.f -> slow_flush]" in f.message
        assert "(reached at fix.py:" in f.message

    def test_host_sync_under_lock_tp(self):
        # the table.py pattern this rule caught for real: np.asarray of
        # a possibly-device value inside the hot-path lock
        src = textwrap.dedent("""
            import threading
            import numpy as np

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def push(self, grad):
                    with self._lock:
                        self.w -= np.asarray(grad)
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL019" and "np.asarray" in f.message

    def test_pragma_suppresses(self):
        src = self.LOCKED.replace(
            "time.sleep(0.1)",
            "time.sleep(0.1)  # tpu-lint: ignore[PTL019]")
        assert lint_source(src, path="fix.py") == []


# ---------------------------------------------------------------------------
# PTL020 — thread lifecycle
# ---------------------------------------------------------------------------

class TestPTL020:
    def test_leaked_thread_tp(self):
        src = textwrap.dedent("""
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL020"
        assert "self._t" in f.message and "never joined" in f.message

    def test_daemon_ctor_tn(self):
        src = textwrap.dedent("""
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self._run,
                                               daemon=True)
                    self._t.start()
        """)
        assert lint_source(src, path="fix.py") == []

    def test_daemon_attr_tn(self):
        src = textwrap.dedent("""
            import threading

            def spawn(fn):
                t = threading.Thread(target=fn)
                t.daemon = True
                t.start()
        """)
        assert lint_source(src, path="fix.py") == []

    def test_joined_tn(self):
        src = textwrap.dedent("""
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def close(self):
                    self._t.join()
        """)
        assert lint_source(src, path="fix.py") == []

    def test_inline_start_tp(self):
        src = textwrap.dedent("""
            import threading

            def fire(fn):
                threading.Thread(target=fn).start()
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL020"

    def test_timer_leak_tp(self):
        # the exact bug this rule caught in tests/test_native_runtime.py
        src = textwrap.dedent("""
            import threading

            def later(fn):
                t = threading.Timer(0.2, fn)
                t.start()
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL020"

    def test_start_in_step_loop_tp(self):
        src = textwrap.dedent("""
            import threading

            def drive(reqs, params):
                for r in reqs:
                    out = decode_step(params, r)
                    threading.Thread(target=print, args=(out,)).start()
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL020" and "step-dispatch loop" in f.message

    def test_pragma_suppresses(self):
        src = textwrap.dedent("""
            import threading

            def fire(fn):
                threading.Thread(target=fn).start()  # tpu-lint: ignore[PTL020]
        """)
        assert lint_source(src, path="fix.py") == []


# ---------------------------------------------------------------------------
# PTL021 — unbounded queue fed from a step-dispatch loop
# ---------------------------------------------------------------------------

class TestPTL021:
    def test_unbounded_put_in_step_loop_tp(self):
        src = textwrap.dedent("""
            import queue

            class S:
                def __init__(self):
                    self._q = queue.Queue()

                def drive(self, reqs, params):
                    for r in reqs:
                        out = decode_step(params, r)
                        self._q.put(out)
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL021"
        assert "self._q" in f.message and "no maxsize" in f.message

    def test_bounded_tn(self):
        src = textwrap.dedent("""
            import queue

            class S:
                def __init__(self):
                    self._q = queue.Queue(maxsize=64)

                def drive(self, reqs, params):
                    for r in reqs:
                        out = decode_step(params, r)
                        self._q.put(out)
        """)
        assert lint_source(src, path="fix.py") == []

    def test_non_step_loop_tn(self):
        # no compiled-step dispatch in the loop: a plain pump may use an
        # unbounded queue
        src = textwrap.dedent("""
            import queue

            class S:
                def __init__(self):
                    self._q = queue.Queue()

                def pump(self, items):
                    for it in items:
                        self._q.put(it)
        """)
        assert lint_source(src, path="fix.py") == []

    def test_simplequeue_tp(self):
        # SimpleQueue has no maxsize at all — always unbounded
        src = textwrap.dedent("""
            import queue

            class S:
                def __init__(self):
                    self._q = queue.SimpleQueue()

                def drive(self, reqs, params):
                    for r in reqs:
                        out = decode_step(params, r)
                        self._q.put(out)
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL021"

    def test_maxsize_zero_tp(self):
        # maxsize=0 is stdlib spelling for "unbounded"
        src = textwrap.dedent("""
            import queue

            class S:
                def __init__(self):
                    self._q = queue.Queue(maxsize=0)

                def drive(self, reqs, params):
                    for r in reqs:
                        out = decode_step(params, r)
                        self._q.put(out)
        """)
        (f,) = lint_source(src, path="fix.py")
        assert f.rule == "PTL021"


# ---------------------------------------------------------------------------
# concurrency audit regression: the serving plane stays clean under the
# v3 rules (the pop-under-lock / send-outside transport design, the
# worker loop, and the fleet parent all hold up)
# ---------------------------------------------------------------------------

class TestServingConcurrencyClean:
    SERVING = ["paddle_tpu/serving/transport.py",
               "paddle_tpu/serving/worker.py",
               "paddle_tpu/serving/launch.py"]

    def test_serving_modules_clean(self):
        files = {}
        for rel in self.SERVING:
            with open(os.path.join(REPO, rel)) as f:
                files[rel] = f.read()
        found = [f for f in lint_project_sources(files)
                 if f.rule in ("PTL018", "PTL019", "PTL020", "PTL021")]
        assert found == [], [f.message for f in found]

    def test_ps_table_clean(self):
        # regression for the real PTL019 catches: DenseTable.push /
        # GraphTable.get_degree / GraphTable.save now convert outside
        # the lock
        with open(os.path.join(REPO,
                               "paddle_tpu/distributed/ps/table.py")) as f:
            src = f.read()
        found = [f for f in lint_source(src, path="table.py")
                 if f.rule == "PTL019"]
        assert found == [], [f.message for f in found]


# ---------------------------------------------------------------------------
# SARIF 2.1.0 reporter
# ---------------------------------------------------------------------------

class TestSarif:
    DIRTY2 = textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return int(x)
    """)

    def _log(self, new, baselined=()):
        return json.loads(format_sarif(new, baselined))

    def test_schema_shape(self):
        # golden schema-shape: the envelope keys a SARIF consumer
        # requires, in the exact places it requires them
        findings = lint_source(self.DIRTY2, path="pkg/f.py")
        log = self._log(findings)
        assert log["$schema"].endswith("sarif-schema-2.1.0.json")
        assert log["version"] == "2.1.0"
        (run,) = log["runs"]
        driver = run["tool"]["driver"]
        assert driver["name"] == "tpu-lint"
        assert {r["id"] for r in driver["rules"]} == set(RULES)
        for r in driver["rules"]:
            assert r["shortDescription"]["text"]
            assert r["fullDescription"]["text"]
            assert r["defaultConfiguration"]["level"] in ("error",
                                                          "warning")
        assert run["columnKind"] == "utf16CodeUnits"
        (res,) = run["results"]
        assert res["ruleId"] == "PTL001" and res["level"] == "error"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "pkg/f.py"
        assert loc["region"]["startLine"] == 6
        assert loc["region"]["startColumn"] >= 1
        assert "suppressions" not in res

    def test_fingerprints_match_baseline(self):
        findings = lint_source(self.DIRTY2, path="pkg/f.py")
        log = self._log(findings)
        (res,) = log["runs"][0]["results"]
        assert res["partialFingerprints"]["tpuLint/v1"] == \
            fingerprints(findings)[0]

    def test_baselined_as_suppressed(self):
        findings = lint_source(self.DIRTY2, path="pkg/f.py")
        log = self._log([], baselined=findings)
        (res,) = log["runs"][0]["results"]
        assert res["suppressions"] == [
            {"kind": "external", "justification": "tpu-lint baseline"}]

    def test_cli_sarif(self, tmp_path):
        mod = tmp_path / "dirty.py"
        mod.write_text(self.DIRTY2)
        r = _run_cli([str(mod), "--format", "sarif", "--no-baseline"])
        assert r.returncode == 1
        log = json.loads(r.stdout)
        assert log["version"] == "2.1.0"
        assert len(log["runs"][0]["results"]) == 1


# ---------------------------------------------------------------------------
# --fix: mechanical fixits
# ---------------------------------------------------------------------------

class TestFix:
    def test_mutable_default_roundtrip(self):
        src = ("def f(a, b=[], c={'k': 1}):\n"
               "    b.append(a)\n"
               "    return b, c\n")
        fixed, applied = fix_source(src)
        assert [r for r, _ in applied] == ["PTL006", "PTL006"]
        assert "b=None" in fixed and "c=None" in fixed
        assert "if b is None:" in fixed and "if c is None:" in fixed
        # behavior preserved: fresh literal per call
        ns = {}
        exec(fixed, ns)
        assert ns["f"](1) == ([1], {"k": 1})
        assert ns["f"](2) == ([2], {"k": 1})  # no shared default
        # and the finding is actually gone
        assert "PTL006" not in [f.rule
                                for f in lint_source(fixed, path="m.py")]

    def test_docstring_and_kwonly(self):
        src = ('def f(*, xs=[]):\n'
               '    """doc."""\n'
               '    return xs\n')
        fixed, _ = fix_source(src)
        lines = fixed.splitlines()
        assert lines[1] == '    """doc."""'
        assert lines[2] == "    if xs is None:"

    def test_bare_except_roundtrip(self):
        src = ("try:\n    x = 1\nexcept:\n    pass\n")
        fixed, applied = fix_source(src)
        assert applied == [("PTL007", 3)]
        assert "except Exception:" in fixed
        assert lint_source(fixed, path="m.py") == []

    def test_idempotent(self):
        src = ("def f(b=[]):\n"
               "    try:\n"
               "        return b\n"
               "    except:\n"
               "        raise\n")
        once, applied = fix_source(src)
        assert len(applied) == 2
        twice, applied2 = fix_source(once)
        assert twice == once and applied2 == []

    def test_one_liner_skipped(self):
        src = "def f(b=[]): return b\n"
        fixed, applied = fix_source(src)
        assert fixed == src and applied == []

    def test_unparsable_untouched(self):
        src = "def f(:\n"
        assert fix_source(src) == (src, [])

    def test_rule_filter(self):
        src = ("def f(b=[]):\n"
               "    try:\n"
               "        return b\n"
               "    except:\n"
               "        raise\n")
        fixed, applied = fix_source(src, rules={"PTL007"})
        assert [r for r, _ in applied] == ["PTL007"]
        assert "b=[]" in fixed

    def test_thread_daemon_flag(self):
        src = textwrap.dedent("""
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()
        """)
        fixed, applied = fix_source(src)
        assert [r for r, _ in applied] == ["PTL020"]
        assert "threading.Thread(target=self._run, daemon=True)" in fixed
        assert lint_source(fixed, path="m.py") == []

    def test_thread_daemon_flag_skips_explicit_false(self):
        # daemon=False is a deliberate choice — the fixer must not
        # silently flip it; the finding stays for a human
        src = textwrap.dedent("""
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self._run,
                                               daemon=False)
                    self._t.start()
        """)
        fixed, applied = fix_source(src)
        assert fixed == src and applied == []
        assert [f.rule for f in lint_source(src, path="m.py")] \
            == ["PTL020"]

    def test_cli_fix_writes(self, tmp_path):
        mod = tmp_path / "m.py"
        mod.write_text("def f(b=[]):\n    return b\n")
        r = _run_cli([str(mod), "--fix", "--no-baseline"])
        assert r.returncode == 0, r.stderr
        assert "fixed 1 finding(s) in 1 file(s)" in r.stdout
        assert "b=None" in mod.read_text()

    def test_cli_dry_run_diff(self, tmp_path):
        mod = tmp_path / "m.py"
        before = "def f(b=[]):\n    return b\n"
        mod.write_text(before)
        r = _run_cli([str(mod), "--fix", "--dry-run", "--no-baseline"])
        assert r.returncode == 0, r.stderr
        assert "-def f(b=[]):" in r.stdout
        assert "+def f(b=None):" in r.stdout
        assert "would fix 1 finding(s)" in r.stdout
        assert mod.read_text() == before  # nothing written

    def test_cli_dry_run_requires_fix(self, tmp_path):
        r = _run_cli([str(tmp_path), "--dry-run"])
        assert r.returncode == 2 and "--dry-run requires --fix" in r.stderr


# ---------------------------------------------------------------------------
# --jobs: parallel linting must be byte-identical to serial
# ---------------------------------------------------------------------------

class TestParallel:
    def test_serial_parallel_identical(self, tmp_path):
        mods = {
            "a.py": "def f(b=[]):\n    return b\n",
            "b.py": "try:\n    x = 1\nexcept:\n    pass\n",
            "c.py": ("import jax\n\n"
                     "def helper(v):\n    return v.item()\n\n"
                     "@jax.jit\ndef fwd(x):\n    return helper(x)\n"),
            "d.py": "x = (\n",  # syntax error
            "e.py": "y = 1\n",
        }
        for name, src in mods.items():
            (tmp_path / name).write_text(src)
        serial = lint_paths([str(tmp_path)], jobs=1)
        parallel = lint_paths([str(tmp_path)], jobs=4)
        assert [f.as_dict() for f in serial] == \
            [f.as_dict() for f in parallel]
        assert {f.rule for f in serial} >= {"PTL000", "PTL001", "PTL006",
                                            "PTL007"}

    def test_parallel_tree_matches_serial(self):
        tree = os.path.join(REPO, "paddle_tpu", "serving")
        serial = lint_paths([tree], jobs=1)
        parallel = lint_paths([tree], jobs=2)
        assert [f.as_dict() for f in serial] == \
            [f.as_dict() for f in parallel]

    def test_cli_jobs(self, tmp_path):
        mod = tmp_path / "m.py"
        mod.write_text("def f(b=[]):\n    return b\n")
        r = _run_cli([str(mod), "--jobs", "2", "--no-baseline"])
        assert r.returncode == 1
        assert "PTL006" in r.stdout


# ---------------------------------------------------------------------------
# per-path profiles: relaxed rule sets for tests/ and bench scripts
# ---------------------------------------------------------------------------

class TestProfiles:
    def test_profile_selection(self):
        assert profile_of("tests/test_serving.py") == "tests"
        assert profile_of("test_x.py") == "tests"
        assert profile_of("tests/conftest.py") == "tests"
        assert profile_of("benchmark/run.py") == "bench"
        assert profile_of("benchmark/drivers/serve.py") == "bench"
        assert profile_of("paddle_tpu/serving/engine.py") == "default"

    def test_relaxed_rules(self):
        full = rules_for("paddle_tpu/x.py", None)
        relaxed = rules_for("tests/test_x.py", None)
        assert full == set(RULES)
        assert full - relaxed == {"PTL004", "PTL008", "PTL009"}
        # explicit --rules still intersects with the profile
        assert rules_for("tests/test_x.py", ["PTL004", "PTL001"]) == \
            {"PTL001"}

    def test_step_loop_sync_allowed_in_tests(self, tmp_path):
        src = textwrap.dedent("""
            import numpy as np

            def loop(xs):
                for x in xs:
                    out = train_step(x)
                    np.asarray(out)
        """)
        prod = tmp_path / "prod.py"
        prod.write_text(src)
        test = tmp_path / "test_loop.py"
        test.write_text(src)
        assert [f.rule for f in lint_paths([str(prod)])] == ["PTL004"]
        assert lint_paths([str(test)]) == []

    def test_extended_tree_gate(self):
        # the whole-repo gate: paddle_tpu strict, tests/ under their
        # relaxed profile — all clean with no baseline debt
        paths = [os.path.join(REPO, "paddle_tpu"),
                 os.path.join(REPO, "tests")]
        findings = lint_paths(paths)
        msgs = [f"{f.path}:{f.line}: {f.rule} {f.message}"
                for f in findings]
        assert not findings, "\n".join(msgs)


# ---------------------------------------------------------------------------
# rule-inventory agreement + self-lint
# ---------------------------------------------------------------------------

class TestRuleInventory:
    def test_reporters_agree_with_list_rules(self):
        r = _run_cli(["--list-rules"])
        assert r.returncode == 0
        cli_rules = {line.split()[0] for line in r.stdout.splitlines()[1:]
                     if line.strip()}
        json_rules = set(json.loads(format_json([]))["rules"])
        sarif_rules = {rule["id"] for rule in json.loads(
            format_sarif([]))["runs"][0]["tool"]["driver"]["rules"]}
        assert cli_rules == json_rules == sarif_rules == set(RULES)

    def test_fixit_slugs_registered(self):
        from paddle_tpu.analysis.fixes import FIXERS
        advertised = {r.fixit for r in RULES.values() if r.fixit}
        assert advertised == set(FIXERS)
        for slug, rid in FIXERS.items():
            assert RULES[rid].fixit == slug

    def test_self_lint_all_rules(self):
        # the linter's own package, every rule enabled, no profile
        # relaxation and no baseline — it must hold itself to v2
        pkg = os.path.join(REPO, "paddle_tpu", "analysis")
        findings = lint_paths([pkg], rules=sorted(RULES))
        msgs = [f"{f.path}:{f.line}: {f.rule} {f.message}"
                for f in findings]
        assert not findings, "\n".join(msgs)
