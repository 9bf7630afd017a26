"""Per-path rule profiles for tpu-lint.

The whole-tree gate lints three kinds of code with different contracts:

* ``paddle_tpu/`` — production; every rule on (the ``default`` profile).
* ``tests/`` — correctness harnesses that sync on purpose (asserting on
  ``np.asarray`` of a step's output IS the test) and park in
  ``time.sleep`` to provoke timing paths, so the hot-loop pipelining
  rules (PTL004/PTL008) and the label-cardinality rule (PTL009) are off;
  trace hygiene, cache-key completeness and thread safety stay on.
* ``bench*.py`` (``benchmark/`` matches: ``*`` spans ``/``) — measurement
  drivers whose loops sync once per iteration by design (that is the
  measurement); same relaxations.

The table below is the single source of truth, shaped like the
``[tool.tpu-lint.profiles]`` table it would be in a pyproject config;
first matching profile wins, ``default`` (no relaxation) otherwise.
Patterns are ``fnmatch`` globs tested against the canonical path and its
basename.
"""
from __future__ import annotations

from fnmatch import fnmatch

from paddle_tpu.analysis.rules import RULES

__all__ = ["PROFILE_TABLE", "profile_of", "rules_for"]

# [tool.tpu-lint.profiles] ------------------------------------------------
PROFILE_TABLE = {
    "tests": {
        "match": ("tests/*", "test_*.py", "conftest.py"),
        "disable": ("PTL004", "PTL008", "PTL009"),
    },
    "bench": {
        "match": ("bench*.py",),
        "disable": ("PTL004", "PTL008", "PTL009"),
    },
    "default": {
        "match": ("*",),
        "disable": (),
    },
}
# -------------------------------------------------------------------------


def profile_of(path):
    """Name of the first profile whose patterns match ``path`` (tested
    against the full slash-normalized path and the basename)."""
    p = str(path).replace("\\", "/")
    base = p.rsplit("/", 1)[-1]
    for name, prof in PROFILE_TABLE.items():
        for pat in prof["match"]:
            if fnmatch(p, pat) or fnmatch(base, pat):
                return name
    return "default"


def rules_for(path, rules=None):
    """Effective enabled-rule set for ``path``: the requested ``rules``
    (all registered rules when None) minus the path's profile's
    ``disable`` list."""
    enabled = set(rules) if rules is not None else set(RULES)
    return enabled - set(PROFILE_TABLE[profile_of(path)]["disable"])
