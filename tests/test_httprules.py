"""Tier-1's hold on the HTTP world's tests (PERF.md §7, left out of PR 36
(b); done by PR 37).

``benchmarks/tests/test_httprules.py`` holds the plain reference of
``worlds/httprules.py`` (the world ``l7-http`` is built with) against a
loop over its documents' text, its table and reasons against the program's
oracle, every contrast case by hand, requests through the shim's
tokenizer, and the worlds ``build`` has to refuse: 24 cases. ``python -m
pytest benchmarks/tests`` runs them there; tier-1 collects ``tests/``
only, so the driver's count did not guard them. This file brings every one
under tier-1 as it stands, case for case, as ``tests/
test_frames_direction.py`` does for PR 33's: pytest collects a test
function (and a fixture) by the name it finds in a module, wherever it was
defined, and a ``parametrize`` mark rides on the function.

The benchmark's conftest is not loaded here, only imported for its
helpers; the native shim its session fixture builds is built by ``tests/
conftest.py``.
"""

from benchmarks.tests.test_httprules import *  # noqa: F401,F403
