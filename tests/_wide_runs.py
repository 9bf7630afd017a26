"""What a wide prefill run — the chunks a scheduler step spends on ONE
prompt, in one run of the program — must keep true in every serving family:
shared by the families' serving tests (CPU, toy sizes).

``make(budget, **kw)`` builds the family's engine with ``prefill_chunk=P``
and ``max_len=LMAX``; ``prefill_budget=1`` is the engine that runs a chunk a
run, which every wider budget must agree with token for token.
"""
import jax
import numpy as np

from paddle_tpu.serving import Request

P = 16
# not whole chunks, and the rest wider than any engine's headroom plus NEW:
# the longest admissible prompt's padded rows run past the cache, so its
# last run writes rows that must drop
LMAX = 126
NEW = 3
# rows of the prompt under test; None = the longest the engine admits
LENGTHS = (1, P - 1, P, P + 1, 2 * P - 1, 2 * P, 2 * P + 1, 3 * P,
           4 * P + 1, None)
BUDGETS = (2, 4)
# a second prompt admitted beside it: three chunks, so that an odd chunk a
# step has left goes to the next slot, whatever the first prompt's length
PARTNER = 2 * P + 3

_COMPILE = "/jax/core/compile/backend_compile_duration"
_compiled = []          # one entry a backend compilation, once listening
_listeners = []         # JAX keeps a listener for the life of the process


def compiles():
    """Backend compilations of this process since the first call."""
    if not _listeners:
        _listeners.append(
            lambda name, *_, **__: name == _COMPILE and _compiled.append(1))
        jax.monitoring.register_event_duration_secs_listener(_listeners[0])
    return len(_compiled)


def family(engine, model):
    """``make(budget, **kw)`` over a test file's own ``engine(model,
    **kw)`` helper, at this module's geometry."""
    return lambda budget, **kw: engine(model, **{**dict(
        max_len=LMAX, prefill_chunk=P, prefill_budget=budget), **kw})


def prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 200, n).astype(np.int32)


def serve(make, budget, length, new=NEW, **kw):
    """The prompt of ``length`` rows and its partner through a 2-slot
    engine: ``(engine, [request, partner])`` after the run."""
    eng = make(budget, **kw)
    if length is None:
        length = eng._lmax - new - eng._headroom()
        # (a paged engine's span is whole blocks: rows past it drop on
        # the table)
        assert eng._lmax != LMAX or -(-length // P) * P > LMAX
    reqs = [eng.submit(Request(prompt(n, seed), new))
            for seed, n in enumerate((length, PARTNER))]
    eng.run()
    return eng, reqs


def streams(reqs):
    """What a run served: every request's status (``poisoned`` = a finite
    flag that read false) and tokens."""
    return [(r.status, list(r.output_ids)) for r in reqs]


def check_warm_set(make, mon, programs=1, **kw):
    """The warm-set contract on a fresh geometry (three slots: no other
    test's traces answer for it).  After the FIRST scheduler step that
    spends prefill — one chunk of a one-chunk prompt — every width of the
    ladder has been traced (``programs`` = 2 with a resident draft model);
    once that request has run to its end, prompts of 4, 6, 8 chunks
    followed by 1, 3, 5, 7 add no trace and no backend compile."""
    eng = make(4, batch_size=3, **kw)
    assert eng._widths == [1, 2, 4]
    traces = lambda: dict(mon.trace_counts())
    before = traces().get("serving_prefill_chunk", 0)
    eng.submit(Request(prompt(P - 2, 0), NEW))
    eng.step()
    assert traces()["serving_prefill_chunk"] - before == 3 * programs
    eng.run()
    warm, built = traces(), compiles()
    longest = eng._lmax - NEW - eng._headroom()
    assert longest > 7 * P
    reqs = [eng.submit(Request(prompt(min(c * P - 5, longest), c), NEW))
            for c in (4, 6, 8, 1, 3, 5, 7)]
    eng.run()
    assert [r.status for r in reqs] == ["done"] * 7
    assert traces() == warm
    assert compiles() == built
