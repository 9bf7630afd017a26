"""Scheduler: tokens emitted by decode steps per scheduler step over the
window, from the program's counters ``serving_tokens_emitted_total`` and
``serving_steps_total`` (first tokens, which the prefill emits, taken off)."""


def read(ctx):
    tokens, steps, _ = ctx["record"]["counters"]
    firsts = sum(1 for r in ctx["record"]["requests"] if r.t_first is not None)
    return (tokens - firsts) / steps if steps and tokens > firsts else None
