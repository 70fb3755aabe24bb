"""The suite's single import point for hypothesis.

Test modules import ``given``/``settings``/``st``/``example`` from here
instead of from ``hypothesis`` directly, so the determinism rule below
applies to every property test.

**Determinism contract.** Hypothesis randomizes its search by default,
which would make the default lane flaky-by-design, so when ``CI`` is set
in the environment every profile the suite registers is forced to
``derandomize=True`` — each run replays the same example sequence. Escape
hatch for counterexample *hunting*: run locally without ``CI``, or pass
hypothesis' builtin ``pytest --hypothesis-seed=<n>`` to pin a specific
randomized search. Counterexamples worth keeping are pinned with
``@example`` on the property itself.
"""
from __future__ import annotations

import os

from hypothesis import example, given, settings  # noqa: F401
import hypothesis.strategies as st  # noqa: F401

if os.environ.get("CI"):  # pragma: no cover - CI-lane only
    _register = settings.register_profile

    def _register_derandomized(name, parent=None, **kw):
        kw.setdefault("derandomize", True)
        return _register(name, parent=parent, **kw)

    settings.register_profile = _register_derandomized
    settings.register_profile("ci", deadline=None)
    settings.load_profile("ci")
